"""Inference engine (v1).

Analog of the reference ``deepspeed/inference/engine.py:39`` (``InferenceEngine``:
TP-sharded, kernel-injected generation; ``_create_model_parallel_group:253``,
CUDA-graph capture :523). TPU-native equivalents: TP sharding is a set of
NamedShardings over the ``model`` mesh axis (no module surgery — the natural
"kernel injection" on TPU is XLA fusing the jitted decode step, and the graph
capture knob is subsumed by jit), and generation is a compiled
prefill + ``lax.scan`` decode loop over a preallocated KV cache.
"""

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .config import DeepSpeedInferenceConfig
from ..parallel import groups
from ..parallel.mesh import MeshConfig, DATA_AXIS, MODEL_AXIS
from ..runtime.zero.partition import PartitionRules
from ..utils.logging import log_dist


class InferenceEngine:

    def __init__(self, model, config: Optional[DeepSpeedInferenceConfig] = None, params=None, mesh=None):
        """``model``: framework model object (TransformerLM) — must expose
        ``config``/``init``; ``params``: optional pre-trained params pytree."""
        self.module = model
        self._config = config or DeepSpeedInferenceConfig()
        tp = max(1, self._config.tensor_parallel.tp_size)

        if mesh is not None:
            self.mesh = groups.set_mesh(mesh)
        elif groups.is_initialized():
            self.mesh = groups.get_mesh()
        else:
            self.mesh = groups.initialize_mesh(MeshConfig(data=-1, model=tp))

        self.model_config = getattr(model, "config", None)
        if self.model_config is not None:
            self.model_config.dtype = self._config.compute_dtype

        rules = model.partition_rules() if hasattr(model, "partition_rules") else PartitionRules()
        self._param_rules = rules
        self.params = self._maybe_quantize(self._place_params(params))
        self._compiled: Dict[Any, Any] = {}
        self._cache = None
        self._model_profile_enabled = False
        self._model_times = []
        log_dist(f"InferenceEngine ready: tp={tp} dtype={self._config.dtype} "
                 f"quant={self._config.quant.enabled} mesh={dict(self.mesh.shape)}", ranks=[0])

    def _place_params(self, params):
        if params is None:
            params = jax.jit(lambda r: self.module.init(r, None))(jax.random.PRNGKey(0))
        specs = self._param_rules.tree_specs(params)
        shardings = jax.tree_util.tree_map(lambda s: NamedSharding(self.mesh, s), specs,
                                           is_leaf=lambda x: isinstance(x, P))
        # device_put (not a jit identity with out_shardings): checkpoint
        # loads arrive committed to one device, which jit rejects against a
        # multi-device mesh; device_put reshards from any source placement
        return jax.device_put(params, shardings)

    # ------------------------------------------------------------------
    def forward(self, input_ids):
        """Plain forward → logits (reference engine __call__ path)."""
        from ..models.transformer import forward as model_forward

        if "fwd" not in self._compiled:
            self._compiled["fwd"] = jax.jit(lambda p, ids: model_forward(self.model_config, p, ids))
        t0 = time.time() if self._model_profile_enabled else None
        with self.mesh:
            out = self._compiled["fwd"](self.params, jnp.asarray(input_ids))
        if t0 is not None:
            jax.block_until_ready(out)
            self._model_times.append(time.time() - t0)
        return out

    __call__ = forward

    # ------------------------------------------------------------------
    def profile_model_time(self, use_cuda_events: bool = True):
        """Enable per-forward wall-clock capture (reference
        ``engine.py:203`` — its CUDA-event hooks become a host-fetch
        barrier here; ``use_cuda_events`` kept for signature parity)."""
        self._model_profile_enabled = True

    def model_times(self):
        """Drain captured per-forward latencies (reference ``engine.py:552``)."""
        assert self._model_profile_enabled, "model profiling is not enabled"
        times, self._model_times = self._model_times, []
        return times

    # ------------------------------------------------------------------
    def generate(self, input_ids, max_new_tokens: int = 32, temperature: float = 0.0, top_k: int = 0,
                 eos_token_id: Optional[int] = None, seed: int = 0):
        """Greedy / sampled generation with a preallocated KV cache.

        input_ids: [B, S_prompt] (right-aligned, no padding support yet).
        Returns [B, S_prompt + max_new_tokens].
        """
        from ..models.transformer import init_kv_cache, forward_with_cache
        from ..monitor.metrics import get_metrics
        from ..monitor.trace import get_tracer

        observing = get_tracer().enabled or get_metrics().enabled
        t0 = time.perf_counter() if observing else 0.0
        cfg = self.model_config
        input_ids = np.asarray(input_ids)
        B, S = input_ids.shape
        max_len = S + max_new_tokens
        key = (B, S, max_new_tokens, float(temperature), int(top_k))

        if key not in self._compiled:

            def gen_fn(params, prompt, rng):
                cache = init_kv_cache(cfg, B, max_len)
                logits, cache = forward_with_cache(cfg, params, prompt, cache)
                next_tok = _select(logits[:, -1], rng, temperature, top_k)

                def step(carry, _):
                    cache, tok, rng = carry
                    rng, sub = jax.random.split(rng)
                    logits, cache = forward_with_cache(cfg, params, tok[:, None], cache)
                    nxt = _select(logits[:, -1], sub, temperature, top_k)
                    return (cache, nxt, rng), nxt

                rng, sub = jax.random.split(rng)
                (_, _, _), toks = jax.lax.scan(step, (cache, next_tok, sub), None, length=max_new_tokens - 1)
                return jnp.concatenate([next_tok[:, None], toks.T], axis=1)

            self._compiled[key] = jax.jit(gen_fn)

        with self.mesh:
            out = self._compiled[key](self.params, jnp.asarray(input_ids), jax.random.PRNGKey(seed))
        out = np.asarray(out)
        if eos_token_id is not None:
            # truncate after first eos per sequence (host-side post-process)
            for b in range(B):
                hits = np.where(out[b] == eos_token_id)[0]
                if hits.size:
                    out[b, hits[0] + 1:] = eos_token_id
        if observing:
            from ..monitor.trace import observe_latency

            observe_latency(t0, "serving/generate", hist_name="serving/generate_ms",
                            gauges={"serving/generate_tokens_per_sec":
                                    lambda dt: B * max_new_tokens / max(dt, 1e-9)},
                            span_args={"batch": int(B), "new_tokens": int(max_new_tokens)})
        return np.concatenate([input_ids, out], axis=1)

    # ------------------------------------------------------------------
    def load_checkpoint(self, path, template=None):
        """Load params from an engine checkpoint (reference
        ``load_model_with_checkpoint:330``)."""
        from ..runtime.checkpoint_engine.orbax_checkpoint_engine import OrbaxCheckpointEngine

        eng = OrbaxCheckpointEngine()
        loaded = eng.load(path, template=template)
        params = loaded.get("module", loaded)
        self.params = self._maybe_quantize(self._place_params(params))
        return self

    def _maybe_quantize(self, params):
        """Apply config.quant to a freshly placed fp tree — used by BOTH
        __init__ and load_checkpoint so a loaded checkpoint cannot silently
        revert a quantized engine to full precision."""
        if not self._config.quant.enabled:
            return params
        from .quantization import quantize_params_for_inference

        return quantize_params_for_inference(params, self._config.quant.num_bits)

    def eval(self):
        return self

    @property
    def config(self):
        return self._config


def _select(logits, rng, temperature, top_k):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)
