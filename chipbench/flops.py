"""Operations and bytes the algorithm needs, computed from shapes alone.

Counts are for the model as the configuration states it: real rows only
(no padded slots or padded prompt positions), each weight read once per
step, and the keys and values of each live row at its own context length.
A matrix product of m x k by k x n counts 2mkn operations.
"""

from __future__ import annotations

from chipbench.reference import Dims


def _dims(conf) -> Dims:
    return conf if isinstance(conf, Dims) else Dims.of(conf)


def layer_matmul_params(conf) -> int:
    m = _dims(conf)
    q, kv = m.heads * m.hd, m.kv_heads * m.hd
    return m.d * q + 2 * m.d * kv + q * m.d + 3 * m.d * m.ff


def layer_params(conf) -> int:
    """Every weight of one layer: projections, two norms, q/k/v bias."""
    m = _dims(conf)
    q, kv = m.heads * m.hd, m.kv_heads * m.hd
    return layer_matmul_params(m) + 2 * m.d + (q + 2 * kv if m.bias else 0)


def decode_flops(conf, ctx_lens) -> float:
    """One decode step; ``ctx_lens`` is each live row's context including
    the token it adds."""
    m = _dims(conf)
    q = m.heads * m.hd
    per_row = 2 * (m.layers * layer_matmul_params(m) + m.d * m.vocab)
    return float(sum(per_row + 4 * m.layers * q * c for c in ctx_lens))


def decode_bytes(conf, ctx_lens, itemsize: int = 2) -> float:
    """One decode step: every layer's weights, the final norm and the head
    read once, one embedding row per live row, each row's cached keys and
    values read at its own length, and its new key and value written."""
    m = _dims(conf)
    kv = m.kv_heads * m.hd
    weights = m.layers * layer_params(m) + m.d + m.d * m.vocab
    rows = len(ctx_lens)
    cache = sum(2 * m.layers * kv * c for c in ctx_lens)  # c-1 read, 1 new
    return float(itemsize * (weights + rows * m.d + cache))


def prefill_flops(conf, prompt_len: int) -> float:
    """One prompt: every position through every layer (causal attention
    over the positions before it) and the head at the last position."""
    m = _dims(conf)
    q = m.heads * m.hd
    p = prompt_len
    return float(2 * p * m.layers * layer_matmul_params(m)
                 + 4 * m.layers * q * p * (p + 1) // 2
                 + 2 * m.d * m.vocab)


def least_time(flops: float, nbytes: float, peaks: dict,
               chips: int = 1) -> float:
    """Seconds the chips need at best, split evenly: the larger of the
    compute bound and the memory bound."""
    return max(flops / (chips * peaks["bf16_flops_per_s"]),
               nbytes / (chips * peaks["hbm_bytes_per_s"]))
