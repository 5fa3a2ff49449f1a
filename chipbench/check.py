"""The comparison that decides ``correct``: served tokens against the
float32 reference, the configuration's reference module.

For each sampled request the reference reads the prompt followed by the
tokens the program served (all but the last) and, at each position where
the program served a token, measures how far that token's logit lies
below the reference's best logit.  A greedy server that computes what the
configuration states serves near-best tokens; the widest gap over the
sample is the number compared.  Logits are compared, not token ids: with
random weights near-ties are common and a rounding flips the argmax.
"""

from __future__ import annotations

import numpy as np

PAD_MULTIPLE = 128  # rows are padded to one length: few compiled shapes


def batch(samples):
    """samples: [(prompt ids, served ids)] -> (tokens [B, T], targets
    [B, T] with -1 where nothing was served)."""
    lens = [len(p) + len(s) - 1 for p, s in samples]
    t = -(-max(lens) // PAD_MULTIPLE) * PAD_MULTIPLE
    tokens = np.zeros((len(samples), t), np.int32)
    targets = np.full((len(samples), t), -1, np.int64)
    for i, (p, s) in enumerate(samples):
        seq = np.concatenate([np.asarray(p), np.asarray(s[:-1], np.int64)])
        tokens[i, :len(seq)] = seq
        targets[i, len(p) - 1:len(p) - 1 + len(s)] = s
    return tokens, targets


def widest_gaps(ref, conf: dict, seed: int, samples, control: bool = False):
    """Widest gap of the served tokens by the reference module ``ref``.
    With ``control`` the fp8 control stands in the program's place: at the
    same positions of the same prompts and served tokens, the tokens it
    puts first are measured instead of the served ones.  Returns a dict of
    floats (logit units)."""
    tokens, targets = batch(samples)
    served = targets >= 0
    picks = targets
    if control:
        _, ctrl_first, _ = ref.score(conf, seed, tokens, targets[None],
                                     quant="fp8")
        picks = np.where(served, ctrl_first, -1)
    best, _, got = ref.score(conf, seed, tokens, picks[None])
    gaps = best - got[0]
    return {"max_logit_gap": float(gaps[served].max()),
            "tokens_checked": int(served.sum())}
