"""Ring attention — blockwise context parallelism over the ``seq`` mesh axis.

The reference fork's long-context answer is Ulysses all-to-all
(``deepspeed/sequence/layer.py``; SURVEY.md §2.2 notes ring/blockwise variants
are absent there and that a ring implementation is the TPU-idiomatic
addition). Ring attention removes Ulysses' head-count ceiling: sequence
parallel degree can exceed the number of KV heads because the sequence stays
sharded end-to-end and only K/V blocks rotate around the ring.

Design (TPU-first):
  - Each device in the ``seq`` axis holds a contiguous shard of the sequence
    [B, S/P, n, d].  K and V shards rotate ring-wise with ``lax.ppermute``
    (neighbor hops = pure ICI traffic, bandwidth-optimal like the
    reference's NCCL p2p pipeline but compiler-scheduled).
  - Attention is accumulated with a streaming (online) softmax across ring
    steps — the cross-device generalization of the flash-attention update,
    so per-device memory is O(S/P · d), never O(S²).
  - The whole loop is a ``lax.scan`` body inside ``shard_map``: one compiled
    program, XLA overlaps the ppermute for step i+1 with the matmuls of step
    i (double-buffered by construction: the permute result is only consumed
    next iteration).
  - Differentiable by construction (scan + ppermute transpose natively);
    ``jax.checkpoint`` on the step body keeps backward memory at one ring
    step's activations.

Usage: inside ``shard_map`` over a mesh with a ``seq`` axis, or via
``ring_attention_gspmd`` which wraps the shard_map for you on sharded global
arrays.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..parallel.mesh import SEQ_AXIS, BATCH_AXES, MODEL_AXIS


def ring_attention(q, k, v, axis_name: str = SEQ_AXIS, causal: bool = True, axis_size: Optional[int] = None,
                   remat: bool = True):
    """Ring attention on per-device shards (call inside ``shard_map``).

    q/k/v: [B, S_local, n_heads, head_dim] — the local sequence shard.
    GQA allowed (k/v may have fewer heads; n_q % n_kv == 0).
    Returns the attention output in the same [B, S_local, n_q, d] layout.
    """
    B, S_loc, nq, d = q.shape
    nkv = k.shape[2]
    assert nq % nkv == 0, f"GQA head mismatch: {nq} % {nkv}"
    g = nq // nkv
    if axis_size is None:
        axis_size = lax.psum(1, axis_name)  # static under shard_map
    P_sz = axis_size
    my_idx = lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(d)

    # GQA stays folded as a grouped einsum — only the raw nkv-head K/V rotate
    # around the ring, so ICI traffic and carry memory are not inflated by the
    # group factor. qt: [B, nkv, g, S_loc, d]; kt/vt: [B, nkv, S_loc, d].
    qt = (q.transpose(0, 2, 1, 3) * scale).astype(jnp.float32).reshape(B, nkv, g, S_loc, d)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    perm = [(j, (j + 1) % P_sz) for j in range(P_sz)]

    def accumulate(k_cur, v_cur, acc, m, l, i):
        # after i hops, we hold the chunk originally at rank (my_idx - i) % P
        src = (my_idx - i) % P_sz
        s = jnp.einsum("bngqd,bnkd->bngqk", qt, k_cur.astype(jnp.float32))
        if causal:
            q_pos = my_idx * S_loc + lax.broadcasted_iota(jnp.int32, (S_loc, S_loc), 0)
            k_pos = src * S_loc + lax.broadcasted_iota(jnp.int32, (S_loc, S_loc), 1)
            s = jnp.where((q_pos >= k_pos)[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bngqk,bnkd->bngqd", p, v_cur.astype(jnp.float32))
        return acc, m_new, l

    if remat:
        accumulate = jax.checkpoint(accumulate)

    def step(carry, i):
        k_cur, v_cur, acc, m, l = carry
        acc, m, l = accumulate(k_cur, v_cur, acc, m, l, i)
        # rotate KV to the next rank; consumed only next iteration so XLA can
        # overlap the ICI transfer with this step's matmuls
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, acc, m, l), None

    # derive from qt so the carries inherit qt's varying manual axes under
    # shard_map (a plain jnp.zeros would be device-invariant and trip scan's
    # carry type check)
    acc0 = jnp.zeros_like(qt)
    m0 = jnp.zeros_like(qt[..., :1]) - 1e30
    l0 = jnp.zeros_like(qt[..., :1])
    # P-1 rotate-and-accumulate steps in a scan, then the last chunk's
    # accumulate outside it — the final ppermute would be dead traffic.
    (kt, vt, acc, m, l), _ = lax.scan(step, (kt, vt, acc0, m0, l0), jnp.arange(P_sz - 1))
    acc, _, l = accumulate(kt, vt, acc, m, l, P_sz - 1)
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, nq, S_loc, d).astype(q.dtype).transpose(0, 2, 1, 3)


class RingAttention:
    """Drop-in alternative to ``DistributedAttention`` (Ulysses) with no
    head-count ceiling on the sequence-parallel degree.

    Unlike Ulysses this ignores the wrapped local attention's internals — the
    blockwise computation *is* the attention — so it takes no
    ``local_attention`` argument; signature otherwise mirrors
    ``sequence.layer.DistributedAttention``.
    """

    def __init__(self, sequence_process_group: str = SEQ_AXIS, causal: bool = True):
        self.spg = sequence_process_group
        self.causal = causal

    def __call__(self, query, key, value, axis_size: Optional[int] = None):
        return ring_attention(query, key, value, axis_name=self.spg, causal=self.causal, axis_size=axis_size)


def ring_attention_gspmd(q, k, v, mesh, causal: bool = True, seq_axis: str = SEQ_AXIS,
                         batch_axes=BATCH_AXES, model_axis: str = MODEL_AXIS):
    """Ring attention on *global* arrays sharded over ``mesh``.

    q/k/v: [B, S, n, d] with B sharded over ``batch_axes``, S over
    ``seq_axis``, heads over ``model_axis`` (TP). Wraps the per-shard kernel
    in ``shard_map``; everything composes with an outer ``jit``.
    """
    spec = P(batch_axes, seq_axis, model_axis, None)
    P_sz = mesh.shape.get(seq_axis, 1)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal, axis_size=P_sz),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
