"""Plain reference of a dense decoder with a SeerAttention-R gate, whose
model RoPE has the published linear scaling.

A copy of ``dense_gate_lm.py`` with one departure: the model's RoPE (q and
k of every layer) rotates position ``t`` by the angles of ``t / factor``,
``factor`` being ``conf["rope_scaling"]["factor"]`` (Hugging Face's
``"linear"`` type; any other type is refused). The gate's own RoPE (Qg and
Kg) is unscaled, at the gate's base, as in the copied file.

Written from the published description, in straightforward ``jax.numpy``
and float32 with every matmul at ``Precision.HIGHEST``; it imports nothing
of the program. It reads the weights the benchmark made (the parameter
tree's names are the interface) and recomputes, over one session's prompt
and served tokens at once, what serving must produce:

* prompt rows: causal attention over every earlier token;
* decode rows (every row at or past the prompt's end): the gate scores
  each 64-token block, block ``j`` by ``Qg(t) . Kg(j) / sqrt(d_gate)``, with
  ``Qg(t)`` the per-KV-head projection of the concatenated pre-RoPE query
  heads of the group, RoPE'd at ``t``, and ``Kg(j)`` the projection of the
  block's pooled pre-RoPE keys ``[max, min, mean]``, RoPE'd at the block's
  first position; the token attends to its first and its last (possibly
  partial) block and to the ``k - 2`` best-scoring complete blocks between
  them, ``k`` being the budget in blocks; with ``k`` or fewer visible
  blocks it attends to all of them. The query heads of a GQA group share
  their KV head's selection.

The model: RMSNorm (eps from the file), Q/K/V projections, per-head
RMSNorm of q and k (``qk_norm``), rotate-half RoPE (linear-scaled), SwiGLU
MLP, final
RMSNorm, tied or untied head.

``precision="fp8"`` is the control: every matmul operand (weights,
activations, attention scores and probabilities) is rounded to float8
e4m3 with an abs-max scale per row of the contraction, products summed in
float32.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


def _q8(x, axes):
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, fp8: bool):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if fp8:
        ins, out = spec.split("->")
        sa, sb = ins.split(",")
        contract = set(sa) & set(sb) - set(out)
        a = _q8(a, tuple(i for i, c in enumerate(sa) if c in contract))
        b = _q8(b, tuple(i for i, c in enumerate(sb) if c in contract))
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta, factor=1.0):
    """x [..., T, H, D], pos [T]: rotate-half RoPE at positions pos /
    factor."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = (pos.astype(jnp.float32) / factor)[:, None] * inv  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


class Dims(NamedTuple):
    """The sizes the reference needs, hashable (a jit static)."""
    h_q: int
    hkv: int
    dh: int
    block: int
    d_gate: int
    k_blocks: int
    eps: float
    rope_theta: float
    rope_factor: float
    gate_theta: float
    gate_rope: bool
    qk_norm: bool

    @classmethod
    def of(cls, conf: Dict[str, Any]) -> "Dims":
        h = conf["num_attention_heads"]
        gate = conf["gate"]
        scaling = conf["rope_scaling"]
        if scaling["type"] != "linear":
            raise ValueError(f"rope_scaling {scaling!r}: only linear")
        return cls(h, conf["num_key_value_heads"],
                   conf.get("head_dim") or conf["hidden_size"] // h,
                   gate["block_size"], gate["d_gate"],
                   max(-(-conf["budget_tokens"] // gate["block_size"]), 2),
                   float(conf["rms_norm_eps"]), float(conf["rope_theta"]),
                   float(scaling["factor"]), float(gate["rope_theta"]),
                   bool(gate["use_rope"]), bool(conf["qk_norm"]))


def _layer(x, lp, prompt_len, dims: Dims, fp8: bool, chunk: int):
    """One decoder layer over the whole padded sequence x [T, d]."""
    h_q, hkv, dh = dims.h_q, dims.hkv, dims.dh
    g = h_q // hkv
    ps, dg, k_blocks, eps = dims.block, dims.d_gate, dims.k_blocks, dims.eps
    t_pad = x.shape[0]
    nb = t_pad // ps
    pos = jnp.arange(t_pad)
    a = lp["attn"]

    hx = _rms(x, lp["ln1"]["scale"], eps)
    q = _mm("td,de->te", hx, a["wq"]["w"], fp8).reshape(t_pad, h_q, dh)
    k = _mm("td,de->te", hx, a["wk"]["w"], fp8).reshape(t_pad, hkv, dh)
    v = _mm("td,de->te", hx, a["wv"]["w"], fp8).reshape(t_pad, hkv, dh)
    if dims.qk_norm:
        q = _rms(q, a["q_norm"]["scale"], eps)
        k = _rms(k, a["k_norm"]["scale"], eps)
    qr = _rope(q, pos, dims.rope_theta, dims.rope_factor)
    kr = _rope(k, pos, dims.rope_theta, dims.rope_factor)

    # gate: Kg per block from pooled pre-RoPE keys, Qg per token
    kb = k.reshape(nb, ps, hkv, dh)
    pooled = jnp.concatenate([kb.max(1), kb.min(1), kb.mean(1)], -1)
    kg = _mm("nhe,hed->nhd", pooled, a["gate"]["wk"], fp8)
    qg = _mm("the,hed->thd", q.reshape(t_pad, hkv, g * dh),
             a["gate"]["wq"], fp8)
    if dims.gate_rope:
        kg = _rope(kg, jnp.arange(nb) * ps, dims.gate_theta)
        qg = _rope(qg, pos, dims.gate_theta)

    def rows(r0):
        t = r0 + jnp.arange(chunk)                              # [C]
        q_c = jax.lax.dynamic_slice_in_dim(qr, r0, chunk)       # [C,H,dh]
        qg_c = jax.lax.dynamic_slice_in_dim(qg, r0, chunk)      # [C,Hkv,dg]
        nv = (t + 1 + ps - 1) // ps                             # visible
        blk = jnp.arange(nb)
        sg = _mm("chd,nhd->hcn", qg_c, kg, fp8) / math.sqrt(dg)
        inner = (blk[None, :] >= 1) & (blk[None, :] < nv[:, None] - 1)
        sg = jnp.where(inner[None], sg, -jnp.inf)
        forced = (blk[None, :] == 0) | (blk[None, :] == nv[:, None] - 1)
        sg = jnp.where(forced[None], jnp.inf, sg)
        kth = jax.lax.top_k(sg, min(k_blocks, nb))[0][..., -1:]
        chosen = (sg >= kth) & (sg > -jnp.inf)                  # [Hkv,C,nb]
        dense = (t < prompt_len) | (nv <= k_blocks)             # [C]
        chosen = chosen | (dense[None, :, None]
                           & (blk[None, None, :] < nv[None, :, None]))
        tok = jnp.repeat(chosen, ps, axis=-1)                   # [Hkv,C,T]
        tok = tok & (pos[None, None, :] <= t[None, :, None])
        s = _mm("cxgd,jxd->xgcj", q_c.reshape(chunk, hkv, g, dh), kr,
                fp8) / math.sqrt(dh)
        s = jnp.where(tok[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = _mm("xgcj,jxd->cxgd", p, v, fp8)
        return o.reshape(chunk, h_q * dh)

    o = jax.lax.map(rows, jnp.arange(0, t_pad, chunk))
    o = o.reshape(t_pad, h_q * dh)
    x = x + _mm("te,ed->td", o, a["wo"]["w"], fp8)
    m = lp["mlp"]
    hx = _rms(x, lp["ln2"]["scale"], eps)
    y = jax.nn.silu(_mm("td,df->tf", hx, m["wi_gate"]["w"], fp8)) \
        * _mm("td,df->tf", hx, m["wi_up"]["w"], fp8)
    return x + _mm("tf,fd->td", y, m["wo"]["w"], fp8)


def _chunk(h_q: int, t_pad: int) -> int:
    """Query rows per attention chunk: scores stay under 512 MiB."""
    c = (1 << 29) // (4 * h_q * t_pad)
    c = 1 << max(c, 1).bit_length() - 1
    while t_pad % c:
        c //= 2
    return max(1, min(c, 512))


@functools.partial(jax.jit, static_argnames=("dims", "n_out", "fp8"))
def _hidden(params, tokens, prompt_len, *, dims: Dims, n_out: int,
            fp8: bool):
    t_pad = tokens.shape[0]
    chunk = _chunk(dims.h_q, t_pad)
    x = jnp.take(params["embed"]["w"], tokens, axis=0).astype(jnp.float32)

    def body(x, lp):
        return _layer(x, lp, prompt_len, dims, fp8, chunk), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = _rms(x, params["final_norm"]["scale"], dims.eps)
    return jax.lax.dynamic_slice_in_dim(x, prompt_len - 1, n_out)


def final_hidden(params, conf: Dict[str, Any], tokens, prompt_len: int,
                 n_out: int, precision: str = "f32"):
    """Final-norm hidden states [n_out, d] of rows prompt_len-1 ...
    prompt_len-2+n_out: the rows whose logits chose served tokens 0 ...
    n_out-1. ``tokens`` is the prompt followed by the served tokens,
    padded to a whole number of blocks that holds ``prompt_len - 1 +
    n_out`` rows (pad ids never reach an earlier row)."""
    if precision not in ("f32", "fp8"):
        raise ValueError(precision)
    return _hidden(params, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(prompt_len, jnp.int32),
                   dims=Dims.of(conf), n_out=int(n_out),
                   fp8=precision == "fp8")


def _head(params):
    if "lm_head" in params:
        return params["lm_head"]["w"], "re,ev->rv"
    return params["embed"]["w"], "re,ve->rv"


@functools.partial(jax.jit, static_argnames=("fp8",))
def _row_stats(params, hidden, tokens, *, fp8: bool):
    w, spec = _head(params)
    lg = _mm(spec, hidden, w, fp8)                           # [rows, V]
    best = jnp.max(lg, -1)
    at = jnp.take_along_axis(lg, tokens[:, None], -1)[:, 0]
    return best, at, jnp.argmax(lg, -1).astype(jnp.int32)


def logit_stats(params, hidden, tokens, precision: str = "f32",
                rows: int = 256):
    """Per row of ``hidden``: (best logit, logit of ``tokens[row]``,
    argmax token), the head applied ``rows`` rows at a time."""
    import numpy as np
    out = [[], [], []]
    for r0 in range(0, hidden.shape[0], rows):
        h = hidden[r0:r0 + rows]
        t = jnp.asarray(tokens[r0:r0 + rows], jnp.int32)
        if h.shape[0] < rows:
            pad = rows - h.shape[0]
            h = jnp.pad(h, ((0, pad), (0, 0)))
            t = jnp.pad(t, (0, pad))
        res = _row_stats(params, h, t, fp8=precision == "fp8")
        n = min(rows, hidden.shape[0] - r0)
        for acc, r in zip(out, res):
            acc.append(np.asarray(r)[:n])
    return tuple(np.concatenate(a) for a in out)
