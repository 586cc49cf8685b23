"""Ring attention: sequence-parallel causal attention over a mesh axis.

Long-context support the reference lacks entirely (SURVEY.md §5.7: the
reference only truncates to block_size). Sequences shard over the mesh's
``sp`` axis; K/V chunks rotate around the ring via ``ppermute`` (ICI
neighbor exchange) while each device accumulates online-softmax statistics —
attention memory per device stays O(T_local), total sequence length scales
with the ring size.

`ring_attention` is written to run inside `shard_map` (it uses
`lax.axis_index`/`lax.ppermute`); `ring_attention_sharded` wraps it for a
given mesh. The plain GSPMD path (all-gather K/V) remains the fallback the
compiler picks when the model runs without the explicit ring (sp axis in
parallel/sharding.py batch specs).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30

# set by the Trainer when cfg.attention_impl == "ring" and a mesh is active;
# the model-level dispatch (ops/attention.py) reads it
_RING: dict = {"mesh": None, "axis": "sp", "batch_axes": ("dp", "fsdp")}


def set_ring_context(mesh: Optional[Mesh], axis_name: str = "sp",
                     batch_axes=("dp", "fsdp")) -> None:
    _RING.update(mesh=mesh, axis=axis_name, batch_axes=batch_axes)


def get_ring_context():
    return _RING["mesh"], _RING["axis"], _RING["batch_axes"]


def _chunk_attention(q, k, v, q_pos, k_pos, scale):
    """One K/V chunk's unnormalized contribution + stats, GQA-aware (no KV
    head expansion).

    q: [B, Tq, KV, G, d]; k, v: [B, Tk, KV, d].
    Returns (o [B, Tq, KV, G, d], m, l both [B, Tq, KV, G, 1]).
    """
    s = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = k_pos[:, None, None, None, :] <= q_pos[:, None, None, :, None]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # [B, KV, G, Tq, 1]
    p = jnp.exp(s - jnp.maximum(m, NEG_INF / 2))
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    perm = (0, 3, 1, 2, 4)
    return o, m.transpose(perm), l.transpose(perm)


def ring_attention(
    q: jnp.ndarray,  # [B, T_local, H, d]  (local sequence shard)
    k: jnp.ndarray,  # [B, T_local, KV, d]
    v: jnp.ndarray,
    axis_name: str = "sp",
) -> jnp.ndarray:
    """Causal ring attention; call under shard_map with sequence sharded on
    `axis_name`. Chunks are laid out contiguously: device i owns global
    positions [i*T_local, (i+1)*T_local)."""
    B, T, H, d = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, T, KV, G, d)
    scale = 1.0 / (d ** 0.5)

    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    q_pos = my * T + jnp.arange(T, dtype=jnp.int32)[None].repeat(B, 0)

    perm = [(i, (i + 1) % n) for i in range(n)]  # send to next, recv from prev

    def step(carry, _):
        kc, vc, src, acc, m_run, l_run = carry
        k_pos = src * T + jnp.arange(T, dtype=jnp.int32)[None].repeat(B, 0)
        o_c, m_c, l_c = _chunk_attention(q, kc, vc, q_pos, k_pos, scale)

        m_new = jnp.maximum(m_run, m_c)
        corr_run = jnp.exp(m_run - m_new)
        corr_c = jnp.exp(m_c - m_new)
        acc = acc * corr_run + o_c * corr_c
        l_run = l_run * corr_run + l_c * corr_c

        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        src = (src - 1) % n  # after rotation we hold the previous device's chunk
        return (kc, vc, src, acc, m_new, l_run), None

    acc0 = jnp.zeros((B, T, KV, G, d), jnp.float32)
    m0 = jnp.full((B, T, KV, G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, T, KV, G, 1), jnp.float32)
    (_, _, _, acc, m_run, l_run), _ = jax.lax.scan(
        step, (k, v, my, acc0, m0, l0), None, length=n
    )
    out = acc / jnp.maximum(l_run, 1e-30)
    return out.reshape(B, T, H, d).astype(q.dtype)


# ------------------------------------------------------- ring of flash

def _ring_steps(axis_name: str):
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return n, my, perm


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_flash(static, qf, kf, vf, seg):
    out, _ = _ring_flash_fwd_impl(static, qf, kf, vf, seg)
    return out


def _ring_flash_fwd_impl(static, qf, kf, vf, seg):
    from datatunerx_tpu.ops.flash_attention import _fwd

    axis_name, block_q, block_k, interpret, H, G = static
    n, my, perm = _ring_steps(axis_name)
    o0, lse0 = _fwd(qf, kf, vf, seg, seg, block_q=block_q, block_k=block_k,
                    interpret=interpret, H=H, G=G, causal=True)
    acc_o = o0.astype(jnp.float32)
    acc_lse = lse0
    if n == 1:
        return acc_o.astype(qf.dtype), acc_lse

    def step(carry, r):
        kc, vc, acc_o, acc_lse = carry
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        # after r rotations this device holds chunk src = (my - r) mod n:
        # strictly past iff my >= r (wrapped chunks are the future — masked)
        o_c, lse_c = _fwd(qf, kc, vc, seg, seg, block_q=block_q,
                          block_k=block_k, interpret=interpret, H=H, G=G,
                          causal=False)
        valid = my >= r
        lse_c = jnp.where(valid, lse_c, -jnp.inf)
        m = jnp.maximum(acc_lse, lse_c)
        wa = jnp.exp(acc_lse - m)
        wb = jnp.exp(lse_c - m)
        denom = wa + wb
        acc_o = (acc_o * wa[..., None]
                 + o_c.astype(jnp.float32) * wb[..., None]) / denom[..., None]
        acc_lse = m + jnp.log(denom)
        return (kc, vc, acc_o, acc_lse), None

    (kc, vc, acc_o, acc_lse), _ = jax.lax.scan(
        step, (kf, vf, acc_o, acc_lse), jnp.arange(1, n))
    return acc_o.astype(qf.dtype), acc_lse


def _ring_flash_vjp_fwd(static, qf, kf, vf, seg):
    out, lse = _ring_flash_fwd_impl(static, qf, kf, vf, seg)
    return out, (qf, kf, vf, seg, out, lse)


def _ring_flash_vjp_bwd(static, res, do):
    """Reverse ring: dq accumulates locally; (dk, dv) accumulators travel
    WITH their K/V chunk around the ring and arrive home after n rotations."""
    from datatunerx_tpu.ops.flash_attention import _bwd

    axis_name, block_q, block_k, interpret, H, G = static
    qf, kf, vf, seg, out, lse = res
    n, my, perm = _ring_steps(axis_name)

    dq0, dk0, dv0 = _bwd(block_q, block_k, interpret, G,
                         (qf, kf, vf, seg, seg, out, lse), do, causal=True)
    dq_acc = dq0.astype(jnp.float32)
    dk_acc = dk0.astype(jnp.float32)
    dv_acc = dv0.astype(jnp.float32)
    if n == 1:
        return dq_acc.astype(qf.dtype), dk_acc.astype(kf.dtype), \
            dv_acc.astype(vf.dtype), None

    def step(carry, r):
        kc, vc, dk_acc, dv_acc, dq_acc = carry
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
        dq_c, dk_c, dv_c = _bwd(block_q, block_k, interpret, G,
                                (qf, kc, vc, seg, seg, out, lse), do,
                                causal=False)
        valid = (my >= r).astype(jnp.float32)
        dq_acc = dq_acc + valid * dq_c.astype(jnp.float32)
        dk_acc = dk_acc + valid * dk_c.astype(jnp.float32)
        dv_acc = dv_acc + valid * dv_c.astype(jnp.float32)
        return (kc, vc, dk_acc, dv_acc, dq_acc), None

    (kc, vc, dk_acc, dv_acc, dq_acc), _ = jax.lax.scan(
        step, (kf, vf, dk_acc, dv_acc, dq_acc), jnp.arange(1, n))
    # one more rotation brings each chunk's accumulator home (n total)
    dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
    dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
    return (dq_acc.astype(qf.dtype), dk_acc.astype(kf.dtype),
            dv_acc.astype(vf.dtype), None)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_flash_attention(
    q: jnp.ndarray,  # [B, T_local, H, d]  (local sequence shard)
    k: jnp.ndarray,  # [B, T_local, KV, d]
    v: jnp.ndarray,
    axis_name: str = "sp",
) -> jnp.ndarray:
    """Ring attention whose per-chunk compute is the Pallas flash kernel:
    O(T_local · block) memory instead of the XLA ring's O(T_local²) score
    tensors (which OOM'd the T=32k AOT certification at 34 GB/step, r5).
    Chunk visibility (self → causal kernel, past → full kernel, wrapped →
    masked out via -inf lse weight) is decided per ring step OUTSIDE the
    kernel, so the kernel itself stays static. Backward runs a reverse ring
    of flash-backward kernels with (dk, dv) accumulators rotating alongside
    their chunk."""
    from datatunerx_tpu.ops.flash_attention import _pick_block

    B, T, H, d = q.shape
    KV = k.shape[2]
    G = H // KV
    block_q = _pick_block(T)
    block_k = _pick_block(T)
    from datatunerx_tpu.ops.flash_attention import _interpret

    static = (axis_name, block_q, block_k, _interpret(), H, G)
    seg = jnp.ones((B, T), jnp.int32)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, T, d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, T, d)
    out = _ring_flash(static, qf, kf, vf, seg)
    return out.reshape(B, H, T, d).transpose(0, 2, 1, 3)


def ring_attention_sharded(
    q: jnp.ndarray,  # [B, T_global, H, d]
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "sp",
    batch_axes=("dp", "fsdp"),
) -> jnp.ndarray:
    """Convenience wrapper: shard_map over (batch, sequence) with KV/head dims
    replicated; tp sharding of heads composes by adding 'tp' to the H spec.

    ``DTX_RING_IMPL`` picks the per-chunk engine: ``flash`` (default — the
    Pallas kernel per chunk, O(T_local) memory) or ``xla`` (the chunked
    einsum reference path, O(T_local²) scores — parity baseline and
    fallback)."""
    import os

    spec_q = P(batch_axes, axis_name, None, None)
    spec_kv = P(batch_axes, axis_name, None, None)
    impl = os.environ.get("DTX_RING_IMPL", "flash").strip().lower()
    base = ring_flash_attention if impl != "xla" else ring_attention
    fn = functools.partial(base, axis_name=axis_name)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec_q, spec_kv, spec_kv),
        out_specs=spec_q,
        check_vma=False,
    )(q, k, v)
