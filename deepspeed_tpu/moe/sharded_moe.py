"""Sharded MoE: gating + expert-parallel dispatch.

TPU-native analog of the reference ``deepspeed/moe/sharded_moe.py``
(``TopKGate:348``, ``top1gating:184``, ``top2gating:282``, ``MOELayer:425``,
``_AllToAll:95``). Parity points kept exactly:

  * top-1 / top-2 gating with capacity factor, load-balancing aux loss
    (`l_aux`), optional random-token-priority (top-1) and second-expert
    normalization (top-2), min-capacity floor, token dropping at capacity.
  * dispatch/combine as einsums against a one-hot "dispatch mask" — the
    reference's own formulation (it einsums with ``sec`` masks), which on TPU
    lands directly on the MXU.
  * expert parallelism over the mesh: experts are sharded over the (data,
    seq) axes — ``lax.all_to_all`` moves token slots between expert shards,
    exactly the reference's ``_AllToAll`` over the EP process group.

Design difference (TPU-idiomatic): everything is fixed-shape — capacity is a
static int, dropped tokens contribute zeros — so the whole layer jits with no
dynamic shapes (the reference also uses fixed capacity; its CUDA path pads the
same way).
"""

import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

uniform_map = {}
gumbel_map = {}
exp_selection_uniform_map = {}


def multiplicative_jitter(x, rng, epsilon=1e-2):
    """Reference ``multiplicative_jitter`` — uniform noise on gate inputs."""
    if epsilon == 0:
        return x
    uniform = jax.random.uniform(rng, x.shape, x.dtype, 1.0 - epsilon, 1.0 + epsilon)
    return x * uniform


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float, min_capacity: int) -> int:
    """Reference ``_capacity`` — tokens per expert buffer size (static)."""
    capacity = math.ceil(num_tokens / num_experts * capacity_factor)
    return max(capacity, min_capacity)


def _one_hot(indices, num_classes, dtype=jnp.float32):
    return jax.nn.one_hot(indices, num_classes, dtype=dtype)


def top1gating(logits: jax.Array,
               capacity_factor: float,
               min_capacity: int,
               used_token=None,
               noisy_gate_policy: Optional[str] = None,
               rng: Optional[jax.Array] = None,
               drop_tokens: bool = True,
               use_rts: bool = True) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
    """Reference ``top1gating:184``. logits: [S, E].

    Returns (l_aux, combine_weights [S, E, C], dispatch_mask [S, E, C], capacity).
    """
    S, E = logits.shape
    capacity = _capacity(S, E, capacity_factor, min_capacity)

    if noisy_gate_policy == "RSample" and rng is not None:
        rng, sub = jax.random.split(rng)
        logits_w_noise = logits + jax.random.gumbel(sub, logits.shape, logits.dtype)
        indices1_s = jnp.argmax(logits_w_noise, axis=1)
    else:
        indices1_s = jnp.argmax(logits, axis=1)
    gates = jax.nn.softmax(logits, axis=1)
    mask1 = _one_hot(indices1_s, E)

    if used_token is not None:
        mask1 = mask1 * used_token[:, None]

    # load-balancing aux loss (reference: me*ce*E)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    # random token priority (reference use_rts): random scores break position
    # bias when selecting which tokens win capacity slots
    if use_rts and rng is not None:
        rng, sub = jax.random.split(rng)
        mask1_rand = mask1 * jax.random.uniform(sub, mask1.shape, mask1.dtype)
    else:
        mask1_rand = mask1

    if drop_tokens:
        # rank tokens per expert by priority score (assigned tokens have
        # positive scores and sort first; argsort is stable). A token's rank
        # is its buffer slot; ranks >= capacity drop — fixed-shape
        # formulation of the reference's top-capacity selection.
        order = jnp.argsort(-mask1_rand, axis=0)  # [S, E]: rank -> token
        ranks = jnp.argsort(order, axis=0)  # [S, E]: token -> rank
        within_cap = (ranks < capacity) & (mask1 > 0)
        mask1 = jnp.where(within_cap, mask1, 0.0)
        locations1_s = jnp.sum(ranks * mask1, axis=1)
    else:
        locations1 = jnp.cumsum(mask1, axis=0) - 1
        locations1_s = jnp.sum(locations1 * mask1, axis=1)
        capacity = S  # no dropping: buffers must hold every token

    gates1_s = jnp.sum(gates * mask1, axis=1)  # gate value of kept tokens (0 if dropped)

    loc_oh = _one_hot(locations1_s.astype(jnp.int32), capacity)
    combine_weights = gates1_s[:, None, None] * mask1[:, :, None] * loc_oh[:, None, :]
    dispatch_mask = (combine_weights > 0).astype(logits.dtype)
    return l_aux, combine_weights, dispatch_mask, capacity


def top2gating(logits: jax.Array,
               capacity_factor: float,
               min_capacity: int,
               drop_tokens: bool = True,
               top2_2nd_expert_sampling: bool = True,
               rng: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
    """Reference ``top2gating:282``. logits: [S, E]."""
    S, E = logits.shape
    gates = jax.nn.softmax(logits, axis=1)
    capacity = _capacity(S, E, capacity_factor * 2, min_capacity) if drop_tokens else S

    indices1_s = jnp.argmax(gates, axis=1)
    mask1 = _one_hot(indices1_s, E)

    if top2_2nd_expert_sampling and rng is not None:
        rng, sub = jax.random.split(rng)
        logits2 = logits + jax.random.gumbel(sub, logits.shape, logits.dtype)
    else:
        logits2 = logits
    logits_except1 = jnp.where(mask1 > 0, -jnp.inf, logits2)
    indices2_s = jnp.argmax(logits_except1, axis=1)
    mask2 = _one_hot(indices2_s, E)

    # positions: expert-1 tokens first, expert-2 after (reference ordering)
    locations1 = jnp.cumsum(mask1, axis=0) - 1
    locations2 = jnp.cumsum(mask2, axis=0) - 1 + jnp.sum(mask1, axis=0, keepdims=True)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.mean(me * ce) * E * E

    if drop_tokens:
        mask1 = mask1 * (locations1 < capacity)
        mask2 = mask2 * (locations2 < capacity)

    locations1_s = jnp.sum(locations1 * mask1, axis=1)
    locations2_s = jnp.sum(locations2 * mask2, axis=1)

    # normalize kept gate values
    gates1_s = jnp.sum(gates * mask1, axis=1)
    gates2_s = jnp.sum(gates * mask2, axis=1)
    denom_s = jnp.clip(gates1_s + gates2_s, 1e-9, None)
    gates1_s = gates1_s / denom_s
    gates2_s = gates2_s / denom_s

    loc1_oh = _one_hot(locations1_s.astype(jnp.int32), capacity)
    loc2_oh = _one_hot(locations2_s.astype(jnp.int32), capacity)
    combine1 = gates1_s[:, None, None] * mask1[:, :, None] * loc1_oh[:, None, :]
    combine2 = gates2_s[:, None, None] * mask2[:, :, None] * loc2_oh[:, None, :]
    combine_weights = combine1 + combine2
    dispatch_mask = (combine_weights > 0).astype(logits.dtype)
    return l_aux, combine_weights, dispatch_mask, capacity


class TopKGate:
    """Reference ``TopKGate:348`` — linear gate + top-k routing."""

    def __init__(self, model_dim: int, num_experts: int, k: int = 1, capacity_factor: float = 1.0,
                 eval_capacity_factor: float = 1.0, min_capacity: int = 8, noisy_gate_policy: Optional[str] = None,
                 drop_tokens: bool = True, use_rts: bool = True, top2_2nd_expert_sampling: bool = True):
        assert k in (1, 2), "Only top-1 and top-2 gatings are supported (reference behavior)"
        self.model_dim = model_dim
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.min_capacity = min_capacity
        self.noisy_gate_policy = noisy_gate_policy
        self.drop_tokens = drop_tokens
        self.use_rts = use_rts
        self.top2_2nd_expert_sampling = top2_2nd_expert_sampling

    def init(self, rng):
        w = jax.random.normal(rng, (self.model_dim, self.num_experts), jnp.float32) / math.sqrt(self.model_dim)
        return {"wg": w}

    def __call__(self, params, x, rng=None, train=True):
        """x: [S, M] tokens. Returns (l_aux, combine [S,E,C], dispatch [S,E,C], capacity)."""
        inp = x.astype(jnp.float32)
        if self.noisy_gate_policy == "Jitter" and rng is not None and train:
            rng, sub = jax.random.split(rng)
            inp = multiplicative_jitter(inp, sub)
        logits = inp @ params["wg"].astype(jnp.float32)
        cf = self.capacity_factor if train else self.eval_capacity_factor
        if self.k == 1:
            return top1gating(logits, cf, self.min_capacity, noisy_gate_policy=self.noisy_gate_policy if train else
                              None, rng=rng, drop_tokens=self.drop_tokens, use_rts=self.use_rts and train)
        return top2gating(logits, cf, self.min_capacity, drop_tokens=self.drop_tokens,
                          top2_2nd_expert_sampling=self.top2_2nd_expert_sampling and train, rng=rng)


class MOELayer:
    """Reference ``MOELayer:425`` — dispatch → expert FFN → combine.

    Functional object: ``init(rng)`` makes params (gate + stacked expert FFN
    weights [E_local, ...]); ``__call__(params, x, ...)`` runs the layer.

    Expert parallelism: with ``ep_axis`` set (inside shard_map over a mesh
    whose (data×seq) axes carry ``ep_size`` shards), each shard holds
    ``num_local_experts = E / ep_size`` experts; dispatched slots move between
    shards by ``lax.all_to_all`` before and after the expert FFN — identical
    communication pattern to the reference's ``_AllToAll`` autograd function.
    """

    def __init__(self, gate: TopKGate, hidden_dim: int, ffn_dim: int, num_local_experts: int,
                 ep_axis: Optional[str] = None, ep_size: int = 1, activation: Callable = jax.nn.gelu):
        self.gate = gate
        self.hidden_dim = hidden_dim
        self.ffn_dim = ffn_dim
        self.num_local_experts = num_local_experts
        self.ep_axis = ep_axis
        self.ep_size = ep_size
        self.activation = activation

    def init(self, rng):
        kg, k1, k2 = jax.random.split(rng, 3)
        E, M, F = self.num_local_experts, self.hidden_dim, self.ffn_dim
        return {
            "gate": self.gate.init(kg),
            "experts": {
                "wi": jax.random.normal(k1, (E, M, F), jnp.float32) / math.sqrt(M),
                "wo": jax.random.normal(k2, (E, F, M), jnp.float32) / math.sqrt(F),
            },
        }

    def _expert_ffn(self, eparams, x):
        """x: [E_local, n, C, M] → per-expert FFN via batched einsum (the
        TPU version of the reference's grouped expert GEMM / moe_gemm)."""
        h = jnp.einsum("encm,emf->encf", x, eparams["wi"].astype(x.dtype))
        h = self.activation(h)
        return jnp.einsum("encf,efm->encm", h, eparams["wo"].astype(x.dtype))

    def __call__(self, params, x, rng=None, train=True):
        """x: [S_local, M] (tokens of this shard). Returns (y [S_local, M], l_aux)."""
        S, M = x.shape
        E = self.gate.num_experts
        l_aux, combine, dispatch, capacity = self.gate(params["gate"], x, rng=rng, train=train)

        # dispatch: [S, E, C] x [S, M] → [E, C, M]
        dispatched = jnp.einsum("sec,sm->ecm", dispatch.astype(x.dtype), x)

        if self.ep_axis is not None and self.ep_size > 1:
            # [E, C, M] → [ep, E_local, C, M] slots; a2a swaps the ep dim with
            # the shard dim: every shard ends up with its local experts' slots
            # from ALL shards (reference _AllToAll:95)
            dispatched = dispatched.reshape(self.ep_size, self.num_local_experts, capacity, M)
            dispatched = lax.all_to_all(dispatched, self.ep_axis, split_axis=0, concat_axis=0, tiled=True)
            # now [ep * E_local, C, M] where axis 0 groups = peers' tokens
            dispatched = dispatched.reshape(self.ep_size, self.num_local_experts, capacity, M)
            dispatched = dispatched.transpose(1, 0, 2, 3)  # [E_local, ep, C, M]
            expert_out = self._expert_ffn(params["experts"], dispatched)
            expert_out = expert_out.transpose(1, 0, 2, 3).reshape(self.ep_size * self.num_local_experts, capacity, M)
            expert_out = lax.all_to_all(expert_out, self.ep_axis, split_axis=0, concat_axis=0, tiled=True)
            expert_out = expert_out.reshape(E, capacity, M)
        else:
            expert_out = self._expert_ffn(params["experts"], dispatched[:, None].reshape(
                self.num_local_experts, -1, capacity, M)).reshape(E, capacity, M)

        # combine: [S, E, C] x [E, C, M] → [S, M]
        y = jnp.einsum("sec,ecm->sm", combine.astype(x.dtype), expert_out)
        return y, l_aux
