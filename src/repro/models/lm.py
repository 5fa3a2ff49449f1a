"""Top-level language-model assembly (per-shard SPMD, runs inside shard_map).

Provides the three entry points the launcher lowers:

* :func:`loss_fn` — training forward + vocab-parallel cross-entropy,
* :func:`prefill` — inference prefill building the sharded KV/SSM caches,
* :func:`decode_step` — one-token decode against those caches.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import rms_norm, softcap
from repro.models.transformer import (CONV_K, RunCtx, _unit_and_reps,
                                      attn_block, mamba_block, mlp_block,
                                      moe_block)


# ---------------------------------------------------------------------------
# embedding / head (vocab-parallel over the ring axis)
# ---------------------------------------------------------------------------


def _vocab_contrib(embed, tokens, off):
    """This die's vocab-slice contribution to the embedding of ``tokens``."""
    vloc = embed.shape[0]
    in_range = (tokens >= off) & (tokens < off + vloc)
    ids = jnp.where(in_range, tokens - off, 0)
    x = jnp.take(embed, ids, axis=0)
    return jnp.where(in_range[..., None], x, 0)


def streamed_vocab_embed(ctx: RunCtx, embed, tokens):
    """Vocab-parallel embedding for *sequence-sharded* tokens.

    The (token-block, partial-embedding) pair streams around the TATP ring:
    every die adds its vocab slice's rows as the block passes through, and
    after R one-hop transfers the block arrives home fully embedded.  Memory
    stays O(local block); traffic equals one pass of the activations — the
    tensor-stream analogue of Megatron's lookup+all-reduce.
    """
    r, axis = ctx.r, ctx.axis
    i = lax.axis_index(axis)
    off = i * embed.shape[0]
    perm = [((p - 1) % r, p) for p in range(r)]  # blocks move +1
    tok, acc = tokens, _vocab_contrib(embed, tokens, off)
    for t in range(1, r + 1):
        tok, acc = jax.tree.map(
            lambda z: lax.ppermute(z, axis, perm), (tok, acc))
        if t < r:
            acc = acc + _vocab_contrib(embed, tok, off)
    return acc  # back at the owner, complete


def embed_tokens(ctx: RunCtx, embed, tokens, prefix_embeds=None,
                 pos_offset=0):
    """tokens: [B, s] per-shard; embed: [Vp/R, D] this die's vocab rows."""
    cfg, r = ctx.cfg, ctx.r
    seq_sharded = (ctx.par.strategy == "tatp" and r > 1
                   and ctx.phase != "decode")
    if seq_sharded:
        x = streamed_vocab_embed(ctx, embed, tokens)
    elif r > 1:  # tokens replicated over the ring (megatron / decode)
        i = lax.axis_index(ctx.axis)
        x = _vocab_contrib(embed, tokens, i * embed.shape[0])
        x = lax.psum(x, ctx.axis)
    else:
        x = jnp.take(embed, tokens, axis=0)
    if getattr(cfg, "scale_embed", False):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if prefix_embeds is not None and cfg.frontend_tokens:
        # modality stub: global positions < frontend_tokens come from the
        # precomputed (replicated) frontend embeddings
        f = cfg.frontend_tokens
        s = tokens.shape[1]
        if ctx.par.strategy == "tatp" and r > 1 and ctx.phase != "decode":
            i = lax.axis_index(ctx.axis)
            pos = pos_offset + i * s + jnp.arange(s)
        else:
            pos = pos_offset + jnp.arange(s)
        pref = jnp.take(prefix_embeds, jnp.clip(pos, 0, f - 1), axis=1)
        x = jnp.where((pos < f)[None, :, None], pref.astype(x.dtype), x)
    return x


def lm_head_logits(ctx: RunCtx, params, x):
    cfg = ctx.cfg
    if cfg.tie_embeddings:
        w = params["embed"]  # [Vp/R, D]
        logits = jnp.einsum("bsd,vd->bsv", x, w,
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
    return softcap(logits, cfg.logit_softcap)


def vocab_parallel_xent(ctx: RunCtx, logits, labels, valid):
    """Cross-entropy for ring-*replicated* tokens (megatron / single die).

    logits: [B, s, Vp/R] fp32; labels/valid: [B, s].
    Returns (sum_nll, sum_count).
    """
    cfg, r = ctx.cfg, ctx.r
    vloc = logits.shape[-1]
    i = lax.axis_index(ctx.axis) if r > 1 else 0
    off = i * vloc
    cols = off + jnp.arange(vloc)
    logits = jnp.where(cols[None, None, :] < cfg.vocab_size, logits, -1e30)

    m = jnp.max(logits, axis=-1)
    if r > 1:
        m = lax.pmax(lax.stop_gradient(m), ctx.axis)
    m = lax.stop_gradient(m)  # stability shift only — exact either way
    se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
    if r > 1:
        se = lax.psum(se, ctx.axis)
    lse = jnp.log(se) + m

    in_range = (labels >= off) & (labels < off + vloc)
    local = jnp.where(in_range, labels - off, 0)
    tgt = jnp.take_along_axis(logits, local[..., None], axis=-1)[..., 0]
    tgt = jnp.where(in_range, tgt, 0.0)
    if r > 1:
        tgt = lax.psum(tgt, ctx.axis)

    nll = (lse - tgt) * valid
    return jnp.sum(nll), jnp.sum(valid.astype(jnp.float32))


def streamed_vocab_xent(ctx: RunCtx, params, x, labels, valid):
    """Head + cross-entropy for *sequence-sharded* tokens (TATP mode).

    Activation blocks stream around the ring; each die computes the partial
    (max, sumexp, target-logit) statistics against its vocab slice as blocks
    pass through, and a second ring pass combines the per-slice statistics
    back at each block's owner.  All transfers are one hop; peak memory is a
    single [B, s_loc, Vp/R] logits block — the full [B, s, Vp] logits tensor
    never exists anywhere.
    """
    cfg, r, axis = ctx.cfg, ctx.r, ctx.axis
    tied = cfg.tie_embeddings
    w = params["embed"] if tied else params["lm_head"]
    vloc = w.shape[0] if tied else w.shape[1]
    i = lax.axis_index(axis) if r > 1 else 0
    off = i * vloc
    cols_ok = (off + jnp.arange(vloc)) < cfg.vocab_size

    def slice_stats(xb, lb):
        if tied:
            logits = jnp.einsum("bsd,vd->bsv", xb, w,
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("bsd,dv->bsv", xb, w,
                                preferred_element_type=jnp.float32)
        logits = softcap(logits, cfg.logit_softcap)
        logits = jnp.where(cols_ok[None, None, :], logits, -1e30)
        m = lax.stop_gradient(jnp.max(logits, axis=-1))
        se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
        in_r = (lb >= off) & (lb < off + vloc)
        ids = jnp.where(in_r, lb - off, 0)
        tgt = jnp.take_along_axis(logits, ids[..., None], axis=-1)[..., 0]
        tgt = jnp.where(in_r, tgt, 0.0)
        return m, se, tgt

    if r == 1:
        m, se, tgt = slice_stats(x, labels)
        nll = (jnp.log(se) + m - tgt) * valid
        return jnp.sum(nll), jnp.sum(valid.astype(jnp.float32))

    # pass 1: stream (x, labels) blocks; rank j's stats[t] covers block j−t
    perm_up = [((p - 1) % r, p) for p in range(r)]  # blocks move +1
    blk = (x, labels)
    stats = []
    for t in range(r):
        stats.append(slice_stats(*blk))
        if t < r - 1:
            blk = jax.tree.map(lambda z: lax.ppermute(z, axis, perm_up), blk)

    # pass 2: ring-combine the per-slice stats back to each block's owner
    def combine(a, b):
        (m1, s1, t1), (m2, s2, t2) = a, b
        m = jnp.maximum(m1, m2)
        se = s1 * jnp.exp(m1 - m) + s2 * jnp.exp(m2 - m)
        return m, se, t1 + t2

    perm_dn = [((p + 1) % r, p) for p in range(r)]  # acc moves −1
    acc = stats[r - 1]
    for s in range(1, r):
        acc = jax.tree.map(lambda z: lax.ppermute(z, axis, perm_dn), acc)
        acc = combine(acc, stats[r - 1 - s])
    m, se, tgt = acc
    nll = (jnp.log(se) + m - tgt) * valid
    return jnp.sum(nll), jnp.sum(valid.astype(jnp.float32))


# ---------------------------------------------------------------------------
# block stack
# ---------------------------------------------------------------------------


def _encoder(ctx: RunCtx, params, enc_embeds):
    cfg = ctx.cfg
    x = enc_embeds

    def body(x, p):
        x, _ = attn_block(ctx, p, x, kind="G", pos_offset=0, bidir_self=True)
        x = mlp_block(ctx, p, x)
        return x, None

    f = jax.checkpoint(body) if ctx.par.remat else body
    x, _ = lax.scan(f, x, params["enc"]["blocks"],
                    unroll=bool(ctx.par.unroll_scan))
    return rms_norm(x, params["enc"]["final_ln"], cfg.norm_eps)


def _stack(ctx: RunCtx, params, x, caches=None, cache_len=None,
           enc_out=None):
    """Run the decoder stack.  Returns (x, aux_loss, new_caches)."""
    cfg = ctx.cfg
    unit, reps = _unit_and_reps(cfg)
    shared = params.get("shared")
    has_cache = caches is not None or ctx.phase == "prefill"
    has_cross = cfg.n_enc_layers > 0

    def rep_body(carry, xs):
        x, aux = carry
        p_rep = xs["p"]
        c_rep = xs.get("c")
        new_c: dict[str, Any] = {}
        for pos, kind in enumerate(unit):
            key = f"u{pos}"
            p = shared if kind == "S" else p_rep[key]
            c = c_rep.get(key) if c_rep is not None else None
            if kind in ("G", "L", "S"):
                x, nc = attn_block(ctx, p, x, kind=kind, pos_offset=0,
                                   cache=c, cache_len=cache_len)
                if cfg.is_moe and kind != "S":
                    x, a = moe_block(ctx, p, x)
                    aux = aux + a
                else:
                    x = mlp_block(ctx, p, x)
            elif kind == "M":
                x, nc = mamba_block(ctx, p, x, cache=c, cache_len=cache_len)
            else:
                raise ValueError(kind)
            if has_cache:
                new_c[key] = nc
            if has_cross and kind == "G":
                cx = c_rep.get("cross") if c_rep is not None else None
                x, ncx = attn_block(ctx, p_rep["cross"], x, kind="G",
                                    pos_offset=0, cache=cx,
                                    cache_len=cache_len,
                                    xattn_kv=enc_out, is_cross=True)
                if has_cache:
                    new_c["cross"] = ncx
        return (x, aux), (new_c if has_cache else None)

    xs = {"p": dict(params["layers"])}
    if has_cross:
        xs["p"]["cross"] = params["cross"]
    if caches is not None:
        xs["c"] = caches

    if ctx.par.remat and ctx.phase == "train":
        if ctx.par.remat_policy == "tatp_outputs":
            # save streamed-linear outputs: backward remat never re-streams
            # the weight blocks around the ring (collective-traffic saver,
            # at the cost of keeping those activations)
            pol = jax.checkpoint_policies.save_only_these_names("tatp_y")
            body = jax.checkpoint(rep_body, policy=pol)
        else:
            body = jax.checkpoint(rep_body)
    else:
        body = rep_body
    (x, aux), new_caches = lax.scan(body, (x, jnp.float32(0.0)), xs,
                                    unroll=bool(ctx.par.unroll_scan))
    return x, aux, new_caches


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def loss_fn(ctx: RunCtx, params, batch):
    """Training loss (per-shard).  batch: tokens/labels [B, s] (+ stubs)."""
    cfg = ctx.cfg
    enc_out = None
    if cfg.n_enc_layers:
        enc_out = _encoder(ctx, params, batch["enc_embeds"].astype(ctx.dtype))
    x = embed_tokens(ctx, params["embed"], batch["tokens"],
                     batch.get("prefix_embeds"))
    x, aux, _ = _stack(ctx, params, x, enc_out=enc_out)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    valid = batch.get("valid", jnp.ones_like(batch["labels"],
                                             jnp.float32))
    if ctx.par.strategy == "tatp" and ctx.r > 1:
        nll_sum, cnt = streamed_vocab_xent(ctx, params, x, batch["labels"],
                                           valid)
    else:
        logits = lm_head_logits(ctx, params, x)
        nll_sum, cnt = vocab_parallel_xent(ctx, logits, batch["labels"],
                                           valid)
    aux_total = cfg.aux_coef * aux if cfg.is_moe else 0.0
    return nll_sum, cnt, aux_total


def prefill(ctx: RunCtx, params, batch):
    """Build caches from a full prompt.  Returns (caches, last_logits)."""
    cfg = ctx.cfg
    ctx = RunCtx(cfg, ctx.par, ctx.dist, phase="prefill")
    enc_out = None
    if cfg.n_enc_layers:
        enc_out = _encoder(ctx, params, batch["enc_embeds"].astype(ctx.dtype))
    x = embed_tokens(ctx, params["embed"], batch["tokens"],
                     batch.get("prefix_embeds"))
    x, _, caches = _stack(ctx, params, x, enc_out=enc_out)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    # logits for the final position (lives on the last ring die)
    last = x[:, -1:, :]
    if ctx.par.strategy == "tatp" and ctx.r > 1:
        i = lax.axis_index(ctx.axis)
        last = lax.psum(
            jnp.where(i == ctx.r - 1, last, jnp.zeros_like(last)), ctx.axis)
    logits = lm_head_logits(ctx, params, last)
    return caches, logits


def decode_step(ctx: RunCtx, params, tokens, caches, cache_len):
    """One decode step.  tokens: [B, 1]; caches sharded; cache_len includes
    the token being processed — a scalar (uniform batch) or a [B] vector
    (continuous batching: every in-flight request advances at its own
    context position).  Returns (next_token, logits_loc, caches)."""
    cfg = ctx.cfg
    ctx = RunCtx(cfg, ctx.par, ctx.dist, phase="decode")
    x = embed_tokens(ctx, params["embed"], tokens,
                     pos_offset=cache_len - 1)
    x, _, new_caches = _stack(ctx, params, x, caches=caches,
                              cache_len=cache_len)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = lm_head_logits(ctx, params, x)  # [B, 1, Vp/R]
    # greedy next token across the vocab-parallel shards
    vloc = logits.shape[-1]
    i = lax.axis_index(ctx.axis) if ctx.r > 1 else 0
    cols = i * vloc + jnp.arange(vloc)
    lmask = jnp.where(cols[None, None, :] < cfg.vocab_size, logits, -jnp.inf)
    best = jnp.max(lmask, axis=-1)
    arg = i * vloc + jnp.argmax(lmask, axis=-1)
    if ctx.r > 1:
        gbest = lax.pmax(best, ctx.axis)
        arg = lax.pmin(jnp.where(best >= gbest, arg, jnp.iinfo(jnp.int32).max)
                       .astype(jnp.int32), ctx.axis)
    next_tok = arg.astype(jnp.int32)
    return next_tok, logits, new_caches


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------


def init_cache(ctx: RunCtx, batch_local: int, max_seq: int,
               enc_len: Optional[int] = None):
    """Zero caches (per-shard shapes) matching `_stack`'s scan layout."""
    cfg = ctx.cfg
    unit, reps = _unit_and_reps(cfg)
    r = ctx.r
    sloc = max_seq // r
    dt = ctx.dtype

    def attn_cache():
        return {
            "k": jnp.zeros((batch_local, sloc, cfg.n_kv_heads, cfg.head_dim),
                           dt),
            "v": jnp.zeros((batch_local, sloc, cfg.n_kv_heads, cfg.head_dim),
                           dt),
        }

    def mamba_cache():
        nh_l = cfg.ssm_heads // r
        conv_dim = cfg.d_inner + 2 * cfg.ssm_state
        return {
            "state": jnp.zeros((batch_local, nh_l, cfg.ssm_head_dim,
                                cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((batch_local, CONV_K - 1, conv_dim), dt),
        }

    def one_rep(_):
        c = {}
        for pos, kind in enumerate(unit):
            c[f"u{pos}"] = attn_cache() if kind in ("G", "L", "S") \
                else mamba_cache()
        if cfg.n_enc_layers:
            el = (enc_len or cfg.frontend_tokens) // r
            c["cross"] = {
                "k": jnp.zeros((batch_local, el, cfg.n_kv_heads,
                                cfg.head_dim), dt),
                "v": jnp.zeros((batch_local, el, cfg.n_kv_heads,
                                cfg.head_dim), dt),
            }
        return c

    return jax.vmap(one_rep)(jnp.arange(reps))


def graft_cache_slots(big, small, slots, rows=None):
    """Host-side slot graft: write ``small``'s batch rows into ``big``'s
    batch *slots* (axis 1 of every cache leaf — axis 0 is the layer-scan
    rep dim).

    It states the continuous-batching admission move: a freshly
    prefilled request's prompt-window cache is merged into the resident
    max-seq decode cache at its assigned slot, leaving every other
    in-flight request's state untouched.  Admission itself runs it on the
    device (``launch/serve.py:_graft_to_device``, tested bit for bit
    against this).  Attention K/V leaves copy the prompt window into the
    head of the slot's sequence axis; SSM state/conv leaves
    (context-length-free) copy whole rows.  Operates on host (numpy)
    trees — callers ``device_get`` / ``device_put`` around it to respect
    the decode layout's shardings.

    It is also the KV *migration* move (elastic serving): with ``rows``
    given, survivors of a fault-triggered plan swap copy old-slot →
    new-slot between two full decode caches — there ``small`` is the old
    resident cache, whose batch axis may be *larger* than ``big``'s (a
    shrunken ``max_batch``).  When the sequence windows differ, only the
    common head is copied: admission grafts a prompt window into a longer
    slot, and a (hypothetical) shrink-seq migration must not read past
    the destination window.
    """
    import numpy as np
    rows = list(rows) if rows is not None else list(range(len(slots)))
    slots = list(slots)
    if not slots:
        return jax.tree.map(np.array, jax.device_get(big))

    def one(d, s):
        d = np.array(d)
        s = np.asarray(s)
        if d.ndim >= 3 and d.shape[2] != s.shape[2]:
            w = min(d.shape[2], s.shape[2])
            d[:, slots, :w] = s[:, rows, :w].astype(d.dtype)
        else:
            d[:, slots] = s[:, rows].astype(d.dtype)
        return d

    return jax.tree.map(one, jax.device_get(big), jax.device_get(small))
