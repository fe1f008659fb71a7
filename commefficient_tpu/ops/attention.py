"""Causal softmax attention with grouped key/value heads: one op behind every
attention core of the expert models (`qwen3_next.GatedAttention`,
`lfm2_moe.GroupedQueryAttention`, `nemotron_h.Attention`, and the latent
attention of `glm4_moe_lite` once its one rotary key head is broadcast beside
the latent keys).

`causal_attention(q, k, v, scale=)` takes q [B, T, KV, n, d_qk] and
k [B, T, KV, d_qk], v [B, T, KV, d_v]: each key/value head serves its n query
heads. Two implementations, chosen from the platform and the shapes
(`implementation`):

- "pallas" (a TPU, T a multiple of the kernel's block, the backward
  kernel's whole-sequence blocks within its VMEM): a flash-attention kernel
  pair. The forward kernel walks a query block's key blocks up to the
  diagonal with an online softmax and writes the output and the per-row
  log-sum-exp; the backward kernel walks a key block's query blocks from the
  diagonal down and accumulates dK and dV over the group's query heads and dQ
  over the key blocks, each in a whole-sequence VMEM block. Only the blocks on
  or below the diagonal are computed (the diagonal ones masked), no T x T
  array reaches HBM, and the forward pass keeps the output and the
  log-sum-exp for the backward pass, nothing of T x T. The products' operands
  are rounded to bf16 (what JAX's default precision does to float32 operands
  on the MXU); the running max and sum, the accumulators, the log-sum-exp and
  dS are float32, and so are the output and the three gradients.
- "jnp" (anywhere else): the T x T scores written out, masked, softmaxed and
  recomputed in the backward pass under `jax.checkpoint` (two copies of them
  are kept otherwise). It is the CPU path and the kernel's oracle in the tests.

The two `pallas_call`s sit in the jitted functions `flash_attention_fwd_call`
and `flash_attention_bwd_call`: a profiler trace names a Pallas call after its
jitted wrapper, and `benchmark/layer_metrics/attention_kernel_roofline.py`
matches those names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# contract the last dim of both operands: a @ b.T
_NT = (((1,), (1,)), ((), ()))
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024

# Tokens a query block and a key block hold, in both kernels. Timed on a v5e
# at T = 2,048 for the four layouts the expert models run (d_qk | d_v of
# 64 | 64 with n = 4, 128 | 128 with n = 16, 256 | 256 with n = 8 and n = 1),
# ten combinations of the two kernels' query and key blocks from 128 to
# 1,024: 512 throughout was the fastest or within 3% of it at every layout
# (PERF.md, section 6).
BLOCK = 512


def block(T: int) -> int:
    return min(BLOCK, T)


def _backward_vmem(T: int, d_qk: int, d_v: int) -> int:
    """What the backward kernel keeps whole in VMEM, two buffers each: a
    head's q and dO (bf16) and dQ, a group's dK and dV (float32)."""
    return 2 * T * (2 * d_qk + 2 * d_v + 4 * d_qk + 4 * d_qk + 4 * d_v)


def implementation(T: int, d_qk: int, d_v: int, n: int) -> str:
    """"pallas" on a TPU where T is a multiple of the block and the backward
    kernel's whole-sequence blocks take under half the VMEM it is given,
    "jnp" everywhere else."""
    if not _on_tpu() or T % LANES or T % block(T):
        return "jnp"
    return "pallas" if _backward_vmem(T, d_qk, d_v) <= _VMEM_LIMIT_BYTES // 2 else "jnp"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def causal_attention(q, k, v, *, scale: float):
    """Causal softmax attention of q [B, T, KV, n, d_qk] over k [B, T, KV, d_qk]
    and v [B, T, KV, d_v], scores scaled by `scale`: [B, T, KV, n, d_v]."""
    T, n, d_qk = q.shape[1], q.shape[3], q.shape[4]
    impl = implementation(T, d_qk, v.shape[-1], n)
    if impl == "jnp":
        return reference_attention(q, k, v, scale)
    return _flash(q, k, v, scale, impl == "interpret")


@functools.partial(jax.checkpoint, static_argnums=(3,))
def reference_attention(q, k, v, scale):
    """The T x T scores written out, masked and softmaxed, recomputed in the
    backward pass (two copies of them are kept otherwise)."""
    T = q.shape[1]
    att = jnp.einsum("bqgnd,bkgd->bgnqk", q, k) * scale
    causal = jnp.tril(jnp.ones((T, T), bool))
    att = jax.nn.softmax(jnp.where(causal, att, jnp.finfo(att.dtype).min), axis=-1)
    return jnp.einsum("bgnqk,bkgd->bqgnd", att, v)


# ---- the kernels: q [B, H, T, d_qk], k [B, KV, T, d_qk], v [B, KV, T, d_v],
# head h served by key/value head h // n


def _causal(scores, q0, k0, *, keys_in_rows: bool = False):
    """scores [R, C] of the queries from q0 on and the keys from k0 on (a query
    a row, or with `keys_in_rows` a key a row), every key past its query
    masked."""
    q_axis, k_axis = (1, 0) if keys_in_rows else (0, 1)
    queries = q0 + lax.broadcasted_iota(jnp.int32, scores.shape, q_axis)
    keys = k0 + lax.broadcasted_iota(jnp.int32, scores.shape, k_axis)
    return jnp.where(keys <= queries, scores, _MASKED)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                scale: float, b: int):
    i = pl.program_id(2)
    q = q_ref[...]
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(j, masked: bool):
        start = pl.multiple_of(j * b, b)
        s = lax.dot_general(q, k_ref[pl.ds(start, b), :], _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal(s, i * b, start)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])  # [b, LANES]
        p = jnp.exp(s - _lanes(m_next, b))
        alpha = jnp.exp(m_prev - m_next)
        m_ref[...] = m_next
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
        pv = jnp.dot(p.astype(jnp.bfloat16), v_ref[pl.ds(start, b), :],
                     preferred_element_type=jnp.float32)
        acc_ref[...] = _lanes(alpha, acc_ref.shape[1]) * acc_ref[...] + pv

    # the key blocks wholly below the diagonal, then the one on it
    lax.fori_loop(0, i, lambda j, c: tile(j, False), None)
    tile(i, True)
    l = l_ref[...]
    o_ref[...] = acc_ref[...] / _lanes(l, o_ref.shape[1])
    lse_ref[...] = m_ref[...] + jnp.log(l)


def _lanes(x, width: int):
    """x [R, LANES], every lane alike, as [R, width]."""
    if width <= LANES:
        return x[:, :width]
    return jnp.tile(x, (1, width // LANES))


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale: float, n: int, b: int, T: int):
    h, j = pl.program_id(1), pl.program_id(2)
    k, v = k_ref[...], v_ref[...]

    @pl.when(j == 0)
    def _():
        dq_ref[...] = jnp.zeros(dq_ref.shape, jnp.float32)

    @pl.when(jnp.logical_and(j == 0, h % n == 0))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
        dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

    dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def tile(i, masked: bool):
        start = pl.multiple_of(i * b, b)
        q = q_ref[pl.ds(start, b), :]
        do = do_ref[pl.ds(start, b), :]
        # transposed: a key per row, a query per column, so the log-sum-exp
        # and delta of the queries are rows [1, b]
        s = lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal(s, start, j * b, keys_in_rows=True)
        p = jnp.exp(s - lse_ref[:, pl.ds(start, b)])
        dv_acc[...] += jnp.dot(p.astype(jnp.bfloat16), do, preferred_element_type=jnp.float32)
        dp = lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:, pl.ds(start, b)]) * scale
        dk_acc[...] += jnp.dot(ds.astype(jnp.bfloat16), q, preferred_element_type=jnp.float32)
        dq_ref[pl.ds(start, b), :] += jnp.dot(ds.T.astype(jnp.bfloat16), k,
                                               preferred_element_type=jnp.float32)

    # the query block on the diagonal, then those wholly below it
    tile(j, True)
    lax.fori_loop(j + 1, T // b, lambda i, c: tile(i, False), None)
    rows = pl.ds(pl.multiple_of(j * b, b), b)
    dk_ref[rows, :] += dk_acc[...]
    dv_ref[rows, :] += dv_acc[...]


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT_BYTES)


@functools.partial(jax.jit, static_argnames=("scale", "b", "interpret"))
def flash_attention_fwd_call(q, k, v, *, scale, b, interpret):
    """q, k, v bf16 in the kernel's layout -> (o [B, H, T, d_v] float32,
    log-sum-exp [B, H, T, LANES] float32, every lane alike)."""
    B, H, T, d_qk = q.shape
    KV, d_v = k.shape[1], v.shape[-1]
    n = H // KV
    query = lambda s, h, i: (s, h, i, 0)  # noqa: E731
    group = lambda s, h, i: (s, h // n, 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, b=b),
        grid=(B, H, T // b),
        in_specs=[pl.BlockSpec((None, None, b, d_qk), query),
                  pl.BlockSpec((None, None, T, d_qk), group),
                  pl.BlockSpec((None, None, T, d_v), group)],
        out_specs=[pl.BlockSpec((None, None, b, d_v), query),
                   pl.BlockSpec((None, None, b, LANES), query)],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, d_v), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, T, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((b, LANES), jnp.float32),
                        pltpu.VMEM((b, LANES), jnp.float32),
                        pltpu.VMEM((b, d_v), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=("scale", "b", "interpret"))
def flash_attention_bwd_call(q, k, v, do, lse, delta, *, scale, b, interpret):
    """q, k, v, do bf16 in the kernel's layout, lse and delta [B, H, 1, T]
    float32 -> (dq, dk, dv) float32 in the kernel's layout."""
    B, H, T, d_qk = q.shape
    KV, d_v = k.shape[1], v.shape[-1]
    n = H // KV
    head = lambda s, h, j: (s, h, 0, 0)  # noqa: E731
    group = lambda s, h, j: (s, h // n, 0, 0)  # noqa: E731
    key = lambda s, h, j: (s, h // n, j, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, n=n, b=b, T=T),
        grid=(B, H, T // b),
        in_specs=[pl.BlockSpec((None, None, T, d_qk), head),
                  pl.BlockSpec((None, None, b, d_qk), key),
                  pl.BlockSpec((None, None, b, d_v), key),
                  pl.BlockSpec((None, None, T, d_v), head),
                  pl.BlockSpec((None, None, 1, T), head),
                  pl.BlockSpec((None, None, 1, T), head)],
        out_specs=[pl.BlockSpec((None, None, T, d_qk), head),
                   pl.BlockSpec((None, None, T, d_qk), group),
                   pl.BlockSpec((None, None, T, d_v), group)],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, d_qk), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, T, d_qk), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, T, d_v), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((b, d_qk), jnp.float32),
                        pltpu.VMEM((b, d_v), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


# ---- the op: model layout in and out, float32 at the boundary


def _heads(q):
    """[B, T, KV, n, d] -> [B, KV * n, T, d] bf16."""
    B, T, KV, n, d = q.shape
    return q.astype(jnp.bfloat16).transpose(0, 2, 3, 1, 4).reshape(B, KV * n, T, d)


def _unheads(o, KV: int):
    """[B, KV * n, T, d] -> [B, T, KV, n, d]."""
    B, H, T, d = o.shape
    return o.reshape(B, KV, H // KV, T, d).transpose(0, 3, 1, 2, 4)


def _kv(k):
    """[B, T, KV, d] -> [B, KV, T, d] bf16."""
    return k.astype(jnp.bfloat16).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, scale, interpret):
    return _flash_fwd(q, k, v, scale, interpret)[0]


def _flash_fwd(q, k, v, scale, interpret):
    T, KV = q.shape[1:3]
    qh, kh, vh = _heads(q), _kv(k), _kv(v)
    o, lse = flash_attention_fwd_call(qh, kh, vh, scale=scale, b=block(T), interpret=interpret)
    o = _unheads(o, KV)
    return o, (qh, kh, vh, o, lse[..., 0])


def _flash_bwd(scale, interpret, res, do):
    qh, kh, vh, o, lse = res
    B, T, KV, n = o.shape[:4]
    delta = jnp.sum(o * do, axis=-1)  # [B, T, KV, n]
    delta = delta.transpose(0, 2, 3, 1).reshape(B, KV * n, 1, T)
    dq, dk, dv = flash_attention_bwd_call(
        qh, kh, vh, _heads(do), lse[:, :, None, :], delta, scale=scale, b=block(T),
        interpret=interpret)
    return _unheads(dq, KV), dk.transpose(0, 2, 1, 3), dv.transpose(0, 2, 1, 3)


_flash.defvjp(_flash_fwd, _flash_bwd)
