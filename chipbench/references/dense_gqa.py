"""Reference module of the dense decoder with grouped-query attention.

A configuration names its reference module with ``"reference"``; the
harness finds ``chipbench/references/<name>.py`` by that name.  A module
gives the published keys it passes to the program (``KEYS``), the
refusal of any mechanism it does not compute (``accepts``), its per-layer
weight leaves (``LEAVES``), the plain forward that decides ``correct``
(``score``), the operation and byte counts of the rooflines
(``decode_flops``, ``decode_bytes``, ``prefill_flops``) and the cut of its
widths to test size (``small``).

This one follows the published description of the Llama-style block
that DeepSeek-LLM and Qwen2 share: RMSNorm before attention and before
the MLP, rotary position embedding (rotate-half form, base
``rope_theta``), causal grouped-query attention with optional q/k/v bias,
a SwiGLU MLP (``down(silu(gate(h)) * up(h))``), a final RMSNorm and an
untied head.

It imports nothing of the program under test.  Its weights come from
:mod:`chipbench.weights` by seed, one layer at a time, in the served
dtype and then widened to float32; every matrix product runs at
``Precision.HIGHEST``.  Padding at the end of a row never reaches an
earlier position (the mask is causal), so rows of different lengths share
one padded batch.

``quant="fp8"`` is the control: the same forward with every matrix
product's operands rounded to float8 e4m3 with a scale per row or column,
the step below the configuration's bfloat16.

Operation and byte counts are for the model as the configuration states
it: real rows only (no padded slots or padded prompt positions), each
weight read once per step, and the keys and values of each live row at
its own context length.  A matrix product of m x k by k x n counts 2mkn
operations.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
HEAD_CHUNK = 256  # positions per head matmul: bounds the logits' memory

# published key -> the program's ModelConfig field, beside the keys every
# architecture shares (chipbench/bench.py:SHARED_KEYS)
KEYS = {
    "intermediate_size": "d_ff", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "attention_bias": "qkv_bias",
}
# per-layer leaf -> (fixed id, standard deviation rule of chipbench.weights)
LEAVES = {
    "wq": (10, "fan_in"), "wk": (11, "fan_in"), "wv": (12, "fan_in"),
    "wo": (13, "fan_in"), "ln": (14, "norm"),
    "bq": (15, "bias"), "bk": (16, "bias"), "bv": (17, "bias"),
    "mlp.w_up": (20, "fan_in"), "mlp.w_gate": (21, "fan_in"),
    "mlp.w_down": (22, "fan_in"), "mlp.ln": (23, "norm"),
}


def accepts(cfg) -> None:
    """Raise ``ValueError`` where the program's ModelConfig ``cfg`` has a
    mechanism this reference does not compute."""
    beyond = {
        "family": cfg.family != "dense", "act": cfg.act != "swiglu",
        "layer_pattern": cfg.layer_pattern != "G",
        "sliding_window": cfg.sliding_window is not None,
        "n_experts": cfg.n_experts != 0,
        "attn_softcap": cfg.attn_softcap is not None,
        "logit_softcap": cfg.logit_softcap is not None,
        "scale_embed": cfg.scale_embed, "tie_embeddings": cfg.tie_embeddings,
        "d_head": cfg.head_dim * cfg.n_heads != cfg.d_model,
        "n_enc_layers": cfg.n_enc_layers != 0,
        "frontend": cfg.frontend is not None,
    }
    if any(beyond.values()):
        raise ValueError("the reference covers dense SwiGLU decoders only "
                         f"(not {', '.join(k for k, v in beyond.items() if v)})")


def small(conf: dict) -> dict:
    """``conf`` with every width cut to test size; multi-head attention
    stays multi-head and grouped stays grouped."""
    kv = 4 if conf["num_key_value_heads"] == conf["num_attention_heads"] \
        else 2
    return dict(conf, hidden_size=64, intermediate_size=160,
                num_attention_heads=4, num_key_value_heads=kv)


@dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    heads: int
    kv_heads: int
    ff: int
    vocab: int
    theta: float
    eps: float
    bias: bool
    dtype: str

    @property
    def hd(self) -> int:
        return self.d // self.heads

    @classmethod
    def of(cls, conf: dict) -> "Dims":
        """From a configuration file's published keys."""
        return cls(d=conf["hidden_size"], layers=conf["num_hidden_layers"],
                   heads=conf["num_attention_heads"],
                   kv_heads=conf["num_key_value_heads"],
                   ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                   theta=float(conf["rope_theta"]),
                   eps=float(conf["rms_norm_eps"]),
                   bias=bool(conf["attention_bias"]),
                   dtype=conf["torch_dtype"])

    def layer_shapes(self) -> dict:
        d, q, kv, f = self.d, self.heads * self.hd, self.kv_heads * self.hd, \
            self.ff
        sh = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
              "ln": (d,), "mlp.w_up": (d, f), "mlp.w_gate": (d, f),
              "mlp.w_down": (f, d), "mlp.ln": (d,)}
        if self.bias:
            sh.update(bq=(q,), bk=(kv,), bv=(kv,))
        return sh


def _fp8(x, axis):
    """Round to e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, quant, a_axis, b_axis):
    if quant == "fp8":
        a, b = _fp8(a, a_axis), _fp8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, stored, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + stored)


def _rope(x, pos, theta):
    """x: [B, T, H, hd]; rotate-half form."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]   # [T, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_weights(base, layer, dims: Dims):
    dt = jnp.dtype(dims.dtype)
    return {n: weights.leaf(base, n, s, dt, layer=layer, leaves=LEAVES)
            .astype(jnp.float32) for n, s in dims.layer_shapes().items()}


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(base, layer, x, *, dims: Dims, quant):
    """One decoder layer on x [B, T, d] (float32)."""
    w = _layer_weights(base, layer, dims)
    b, t, _ = x.shape
    hq, hk, hd = dims.heads, dims.kv_heads, dims.hd
    pos = jnp.arange(t)

    h = _rms(x, w["ln"], dims.eps)
    q = _mm("btd,df->btf", h, w["wq"], quant, -1, 0)
    k = _mm("btd,df->btf", h, w["wk"], quant, -1, 0)
    v = _mm("btd,df->btf", h, w["wv"], quant, -1, 0)
    if dims.bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(b, t, hq, hd), pos, dims.theta)
    k = _rope(k.reshape(b, t, hk, hd), pos, dims.theta)
    v = v.reshape(b, t, hk, hd)
    g = hq // hk
    q = q.reshape(b, t, hk, g, hd)
    s = _mm("bqhgd,bkhd->bhgqk", q, k, quant, -1, -1) / np.sqrt(hd)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bhgqk,bkhd->bqhgd", p, v, quant, -1, 1).reshape(b, t, hq * hd)
    x = x + _mm("btf,fd->btd", o, w["wo"], quant, -1, 0)

    h = _rms(x, w["mlp.ln"], dims.eps)
    gate = _mm("btd,df->btf", h, w["mlp.w_gate"], quant, -1, 0)
    up = _mm("btd,df->btf", h, w["mlp.w_up"], quant, -1, 0)
    return x + _mm("btf,fd->btd", jax.nn.silu(gate) * up, w["mlp.w_down"],
                   quant, -1, 0)


@functools.partial(jax.jit, static_argnames=("dims",))
def _embed(base, tokens, *, dims: Dims):
    table = weights.leaf(base, "embed", (dims.vocab, dims.d),
                         jnp.dtype(dims.dtype))
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims",))
def _head_weight(base, *, dims: Dims):
    return weights.leaf(base, "lm_head", (dims.d, dims.vocab),
                        jnp.dtype(dims.dtype)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _head(x, final_ln, head, picks, *, dims: Dims, quant):
    """Per position: the best logit, its id, and the logits of ``picks``
    ([k, N] ids).  x: [N, d]."""
    h = _rms(x, final_ln, dims.eps)
    logits = _mm("nd,dv->nv", h, head, quant, -1, 0)
    got = jnp.take_along_axis(logits[None], picks[..., None], axis=-1)[..., 0]
    return jnp.max(logits, -1), jnp.argmax(logits, -1).astype(jnp.int32), got


def score(conf: dict, seed: int, tokens: np.ndarray, picks: np.ndarray,
          quant=None):
    """Run the reference over ``tokens`` [B, T] and, at every position,
    return (best logit [B, T], best id [B, T], logits of ``picks``
    [k, B, T]).  Pick ids outside the vocabulary read -inf."""
    dims = Dims.of(conf)
    base = weights.seed_key(seed)
    b, t = tokens.shape
    with jax.default_matmul_precision("highest"):
        x = _embed(base, jnp.asarray(tokens, jnp.int32), dims=dims)
        for layer in range(dims.layers):
            x = _layer(base, jnp.int32(layer), x, dims=dims, quant=quant)
        final_ln = weights.leaf(base, "final_ln", (dims.d,),
                                jnp.dtype(dims.dtype)).astype(jnp.float32)
        head = _head_weight(base, dims=dims)
        flat = x.reshape(b * t, dims.d)
        pk = np.asarray(picks, np.int64).reshape(len(picks), b * t)
        valid = (pk >= 0) & (pk < dims.vocab)
        pk = np.where(valid, pk, 0).astype(np.int32)
        best, arg, got = [], [], []
        for lo in range(0, b * t, HEAD_CHUNK):
            hi = min(lo + HEAD_CHUNK, b * t)
            n = hi - lo
            xs = flat[lo:hi]
            pks = pk[:, lo:hi]
            if n < HEAD_CHUNK:  # one compiled shape for every chunk
                xs = jnp.pad(xs, ((0, HEAD_CHUNK - n), (0, 0)))
                pks = np.pad(pks, ((0, 0), (0, HEAD_CHUNK - n)))
            m, a, g = _head(xs, final_ln, head, jnp.asarray(pks), dims=dims,
                            quant=quant)
            best.append(np.asarray(m)[:n])
            arg.append(np.asarray(a)[:n])
            got.append(np.asarray(g)[:, :n])
        del head, x, flat
    got = np.where(valid, np.concatenate(got, 1), -np.inf)
    return (np.concatenate(best).reshape(b, t),
            np.concatenate(arg).reshape(b, t),
            got.reshape(len(picks), b, t))


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
