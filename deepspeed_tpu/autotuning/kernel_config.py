"""Persisted Pallas kernel-config registry + measured-trial tile autotuner.

The survey's op_builder layer (SURVEY.md §2.3) exists because DeepSpeed
treats *tuned* kernels as a first-class subsystem: every CUDA kernel ships
with a build/tune step and the runtime loads the tuned artifact. The TPU
analog: Pallas tile sizes (flash ``block_q``/``block_k``, grouped-matmul
``block_k``/``block_n``, paged-attention ``q_tile``) are the only knobs the
compiler does not pick for us, and the best values depend on chip generation
(VMEM size, MXU shape), topology and shape bucket.

Two pieces:

* :class:`KernelConfigRegistry` — the ONE lookup every tuned ``pallas_call``
  site consults (``tools/check_kernel_configs.py`` gate-enforces this). Keyed
  ``topology -> kernel -> shape_bucket -> param``; topology =
  ``"<device_kind>|n<device_count>"`` so a config tuned on a v5e-8 never
  leaks onto a v4-32. Backed by a ``kernel_config.json`` file (env
  ``DS_TPU_KERNEL_CONFIG``, default ``~/.cache/deepspeed_tpu/``), reloaded by
  mtime so a freshly-written sweep is picked up without a restart. A missing
  file or key falls back to the caller's generation-heuristic default — the
  registry can only ever *improve* on the hardcoded behavior.

* :class:`KernelAutotuner` — the measured-trial sweep (the kernel-level
  analog of ``autotuning/scheduler.py``'s config trials): times each tile
  candidate on the live backend and persists the winners to
  ``<output_dir>/kernel_config.json`` — next to the batch/ZeRO sweep's
  ``best_config.json`` so one tuning run leaves both artifacts.
"""

import json
import math
import os
import threading
import time
from typing import Callable, Dict, Optional, Sequence

from ..utils.logging import logger

_ENV_PATH = "DS_TPU_KERNEL_CONFIG"
CONFIG_FILENAME = "kernel_config.json"


def default_config_path() -> str:
    env = os.environ.get(_ENV_PATH)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "deepspeed_tpu", CONFIG_FILENAME)


def topology_key() -> str:
    """``"<device_kind>|n<devices>"`` — the persistence key: tile winners are
    a property of the chip generation AND the slice size (a different device
    count changes the per-chip shapes the model layer actually runs)."""
    try:
        import jax

        devs = jax.devices()
        kind = str(devs[0].device_kind)
        n = len(devs)
    except Exception:
        kind, n = "unknown", 1
    return f"{kind}|n{n}"


def _pow2_ceil(v: int) -> int:
    v = int(v)
    if v <= 1:
        return max(v, 0)
    return 1 << math.ceil(math.log2(v))


def shape_bucket(**dims) -> str:
    """Canonical shape-bucket key: each dim rounded up to a power of two,
    keys sorted — ``shape_bucket(T=200, d=128) == 'T256|d128'``. Bucketing
    keeps the config table small while matching the serving plane's own
    pow-2 bucket compilation."""
    return "|".join(f"{k}{_pow2_ceil(v)}" for k, v in sorted(dims.items()))


class KernelConfigRegistry:
    """mtime-cached view over ``kernel_config.json``.

    Layout::

        {"version": 1,
         "configs": {"<topology>": {"<kernel>": {"<bucket>": {param: value,
                                                              "_ms": 1.23}}}}}

    ``lookup`` walks topology -> kernel -> (exact bucket, then ``"*"``) and
    returns the caller's default when anything is missing. All mutation goes
    through ``record``/``save`` (atomic tmp+rename) so a crashed sweep can
    never leave a torn file behind.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_config_path()
        self._lock = threading.RLock()
        self._data: Dict = {}
        self._mtime: Optional[float] = None
        self._missing = False

    # -- load / persist ------------------------------------------------
    def _refresh(self):
        try:
            mtime = os.path.getmtime(self.path)
        except OSError:
            if not self._missing:
                self._data, self._mtime, self._missing = {}, None, True
            return
        if self._mtime == mtime and not self._missing:
            return
        try:
            with open(self.path) as f:
                raw = json.load(f)
            self._data = raw.get("configs", {}) if isinstance(raw, dict) else {}
            self._mtime, self._missing = mtime, False
        except (OSError, ValueError) as e:
            logger.warning(f"kernel_config: unreadable {self.path} ({e}); using defaults")
            self._data, self._mtime, self._missing = {}, mtime, False

    def load(self, path: str):
        """Install a sweep artifact (e.g. ``<tune_dir>/kernel_config.json``)
        as this registry's backing file."""
        with self._lock:
            self.path = path
            self._mtime, self._missing = None, False
            self._refresh()
        return self

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with self._lock:
            payload = {"version": 1, "written_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
                       "configs": self._data}
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            self.path = path
            try:
                self._mtime = os.path.getmtime(path)
            except OSError:
                self._mtime = None
            self._missing = False
        return path

    # -- lookup / record ----------------------------------------------
    def lookup(self, kernel: str, bucket: str, param: str, default=None, topo: Optional[str] = None):
        topo = topo or topology_key()
        with self._lock:
            self._refresh()
            node = self._data.get(topo, {}).get(kernel, {})
            for b in (bucket, "*"):
                val = node.get(b, {}).get(param)
                if val is not None:
                    return int(val) if isinstance(default, int) and not isinstance(default, bool) else val
        return default

    def record(self, kernel: str, bucket: str, params: Dict, topo: Optional[str] = None):
        topo = topo or topology_key()
        with self._lock:
            self._refresh()
            self._data.setdefault(topo, {}).setdefault(kernel, {}).setdefault(bucket, {}).update(params)

    def entries(self, topo: Optional[str] = None) -> Dict:
        with self._lock:
            self._refresh()
            return json.loads(json.dumps(self._data.get(topo or topology_key(), {})))

    def clear(self):
        with self._lock:
            self._data, self._mtime, self._missing = {}, None, False


_registry: Optional[KernelConfigRegistry] = None
_registry_lock = threading.Lock()


def get_kernel_registry() -> KernelConfigRegistry:
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = KernelConfigRegistry()
        return _registry


def set_kernel_config_path(path: Optional[str]):
    """Point the process-global registry at ``path`` (None = default path).
    Returns the registry — the test / engine hook for installing a sweep."""
    global _registry
    with _registry_lock:
        _registry = KernelConfigRegistry(path)
        return _registry


def tuned_tile(kernel: str, bucket: str, param: str, default: int) -> int:
    """THE call-site API: every tuned ``pallas_call`` wrapper resolves its
    tile sizes through this (gate-enforced by ``tools/check_kernel_configs.py``).
    Falls back to the caller's generation-heuristic ``default``."""
    return get_kernel_registry().lookup(kernel, bucket, param, default)


# ---------------------------------------------------------------------------
# Measured-trial sweep
# ---------------------------------------------------------------------------

class KernelAutotuner:
    """Times tile candidates on the live backend and persists the winners.

    Off-TPU the kernels run in Pallas interpret mode on tiny shapes — the
    sweep plumbing (candidate set -> timing -> record -> save -> reload) is
    CI-covered even though the recorded numbers only matter on-chip.
    """

    def __init__(self, output_dir: str, registry: Optional[KernelConfigRegistry] = None,
                 steps: int = 5, warmup: int = 2):
        self.output_dir = output_dir
        self.registry = registry or KernelConfigRegistry(
            os.path.join(output_dir, CONFIG_FILENAME))
        self.steps = steps
        self.warmup = warmup
        self.results: Dict[str, Dict] = {}

    @staticmethod
    def _on_tpu() -> bool:
        # decides interpret mode for every sweep: a backend that fails to
        # initialise raises here, it never reads as "not a TPU"
        import jax

        return jax.default_backend() == "tpu"

    def measure(self, fn: Callable[[], object]) -> float:
        """Median-of-steps wall seconds for one candidate callable (each call
        must produce device work; we block on the result)."""
        import jax

        for _ in range(self.warmup):
            jax.block_until_ready(fn())
        times = []
        for _ in range(self.steps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]

    def sweep(self, kernel: str, bucket: str, candidates: Sequence[Dict],
              build: Callable[[Dict], Callable[[], object]]) -> Optional[Dict]:
        """Measure every candidate param-dict; record the fastest. A candidate
        whose build/run raises is skipped (an over-budget tiling must cost a
        candidate, never the sweep)."""
        best, best_t = None, None
        for cand in candidates:
            try:
                t = self.measure(build(cand))
            except Exception as e:
                logger.warning(f"kernel autotune {kernel}[{bucket}] candidate {cand} failed: "
                               f"{type(e).__name__}: {str(e)[:120]}")
                continue
            logger.info(f"kernel autotune {kernel}[{bucket}] {cand}: {t * 1e3:.3f} ms")
            if best_t is None or t < best_t:
                best, best_t = dict(cand), t
        if best is None:
            return None
        self.registry.record(kernel, bucket, {**best, "_ms": round(best_t * 1e3, 4)})
        self.results.setdefault(kernel, {})[bucket] = {**best, "_ms": round(best_t * 1e3, 4)}
        rf_label = f"pallas/{kernel}/{bucket}"
        try:
            from ..monitor.roofline import get_roofline

            rf = get_roofline()
            if rf.enabled:
                # the winner's roofline row: measured median wall + lazy cost
                # of the winning thunk (closed-over operands lower as
                # constants — fine for flop/byte totals)
                rf.note_wall(rf_label, best_t)
                rf.register_thunk(rf_label, build(best))
        except Exception as e:  # noqa: BLE001 — telemetry never costs a sweep
            logger.warning(f"roofline join for {rf_label} failed: "
                           f"{type(e).__name__}: {str(e)[:120]}")
        return best

    # -- per-kernel sweeps --------------------------------------------
    def tune_flash(self, B=1, S=None, nq=8, d=128, candidates=None):
        import jax
        import jax.numpy as jnp

        from ..ops.pallas.flash_attention import _pallas_flash

        on_tpu = self._on_tpu()
        S = S or (2048 if on_tpu else 256)
        if not on_tpu:
            nq, d = 2, 32
        cands = candidates or ([{"block_q": bq, "block_k": bk}
                                for bq in (512, 1024) for bk in (512, 1024)]
                               if on_tpu else
                               [{"block_q": 64, "block_k": 128}, {"block_q": 128, "block_k": 128}])
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        dt = jnp.bfloat16 if on_tpu else jnp.float32
        q = jax.random.normal(k1, (B, S, nq, d), dt)
        k = jax.random.normal(k2, (B, S, nq, d), dt)
        v = jax.random.normal(k3, (B, S, nq, d), dt)

        def build(c):
            return lambda: _pallas_flash(q, k, v, causal=True, block_q=c["block_q"],
                                         block_k=c["block_k"], interpret=not on_tpu)

        return self.sweep("flash_attention", shape_bucket(S=S, d=d), cands, build)

    def tune_paged(self, T=None, n_seqs=4, block_size=None, nq=8, d=128, candidates=None):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..ops.pallas.paged_attention import _pallas_paged

        on_tpu = self._on_tpu()
        # the sweep shape must be PREFILL-ISH (T >= 64 and T >= 2S) on every
        # backend: _resolve_q_tile only consults the T-only bucket for such
        # shapes, so a smaller smoke sweep would record winners no live call
        # can reach
        # on the chip, rows of 256 tokens: long enough for the 128- and
        # 256-token tiles among the candidates to be filled
        T = T or (1024 if on_tpu else 128)
        n_seqs = min(n_seqs, max(1, T // 64))
        bs = block_size or (128 if on_tpu else 16)
        if not on_tpu:
            nq, d = 4, 32
        n_blocks = n_seqs * 4
        rng = np.random.default_rng(0)
        dt = jnp.bfloat16 if on_tpu else jnp.float32
        k_pool = jnp.asarray(rng.normal(size=(n_blocks * bs, nq, d)), dt)
        v_pool = jnp.asarray(rng.normal(size=(n_blocks * bs, nq, d)), dt)
        tables = jnp.arange(n_blocks, dtype=jnp.int32).reshape(n_seqs, -1)
        q = jnp.asarray(rng.normal(size=(T, nq, d)), dt)
        per = T // n_seqs
        seq_idx = jnp.asarray(np.repeat(np.arange(n_seqs), per)[:T], jnp.int32)
        pos = jnp.asarray(np.tile(np.arange(per), n_seqs)[:T] + bs, jnp.int32)
        cands = candidates or [{"q_tile": qt} for qt in ((1, 8, 32, 64, 128, 256) if on_tpu else (1, 4, 8))]

        def build(c):
            return lambda: _pallas_paged(q, k_pool, v_pool, tables, seq_idx, pos,
                                         block_size=bs, q_tile=c["q_tile"],
                                         interpret=not on_tpu)

        # record under the T-only bucket: _resolve_q_tile's S is the live
        # block-table CAPACITY (deployment-dependent), not this sweep's
        # n_seqs — the T-only key is the one every deployment's fallback
        # lookup reaches (exact (T, S) entries can still be hand-recorded)
        return self.sweep("paged_attention", shape_bucket(T=T), cands, build)

    @staticmethod
    def paged_decode_case(on_tpu: bool, n_seqs=4, max_blocks=None, block_size=None,
                          nq=8, d=128):
        """The canonical decode-shaped microbench case (one token per
        sequence, every row at the END of a fully-live ``max_blocks``-block
        context): ``(q, k_pool, v_pool, tables, seq_idx, pos, block_size,
        max_blocks)``. SHARED by :meth:`tune_paged_decode` and
        ``bench.py``'s ``paged_decode_split`` A/B so the bench can never
        quietly measure a different shape than the tuner records."""
        import jax.numpy as jnp
        import numpy as np

        mb = max_blocks or (64 if on_tpu else 32)
        bs = block_size or (128 if on_tpu else 16)
        if not on_tpu:
            nq, d = 4, 32
        rng = np.random.default_rng(0)
        dt = jnp.bfloat16 if on_tpu else jnp.float32
        n_blocks = n_seqs * mb
        k_pool = jnp.asarray(rng.normal(size=(n_blocks * bs, nq, d)), dt)
        v_pool = jnp.asarray(rng.normal(size=(n_blocks * bs, nq, d)), dt)
        tables = jnp.arange(n_blocks, dtype=jnp.int32).reshape(n_seqs, mb)
        q = jnp.asarray(rng.normal(size=(n_seqs, nq, d)), dt)
        seq_idx = jnp.arange(n_seqs, dtype=jnp.int32)
        pos = jnp.full((n_seqs, ), mb * bs - 1, jnp.int32)  # fully-live long context
        return q, k_pool, v_pool, tables, seq_idx, pos, bs, mb

    def tune_paged_decode(self, n_seqs=None, max_blocks=None, block_size=None, nq=8, d=128,
                          candidates=None):
        """Sweep ``kv_splits`` on a DECODE-shaped batch (one token per
        sequence, long block table): 1 is the per-token grid, anything above
        the decode kernel (its grid is the work list of live blocks, so the
        candidates above 1 are one kernel: the sweep decides between two
        grids). Records under the B-only bucket (B = block-table capacity) —
        the key ``_resolve_kv_splits`` falls back to for any decode batch
        size."""
        from ..ops.pallas.paged_attention import _pallas_paged

        on_tpu = self._on_tpu()
        q, k_pool, v_pool, tables, seq_idx, pos, bs, mb = self.paged_decode_case(
            on_tpu, n_seqs=n_seqs or 4, max_blocks=max_blocks, block_size=block_size,
            nq=nq, d=d)
        cands = candidates or [{"kv_splits": ks}
                               for ks in ((1, 4, 8, 16) if on_tpu else (1, 2, 4, 8))]

        def build(c):
            return lambda: _pallas_paged(q, k_pool, v_pool, tables, seq_idx, pos,
                                         block_size=bs, q_tile=1, kv_splits=c["kv_splits"],
                                         interpret=not on_tpu)

        return self.sweep("paged_attention", shape_bucket(B=mb), cands, build)

    def tune_grouped(self, T=None, K=None, N=None, E=4, candidates=None):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..ops.pallas.grouped_matmul import gmm

        on_tpu = self._on_tpu()
        T = T or (1024 if on_tpu else 64)
        K = K or (1024 if on_tpu else 64)
        N = N or (1024 if on_tpu else 64)
        bt = 128 if on_tpu else 8
        rng = np.random.default_rng(0)
        dt = jnp.bfloat16 if on_tpu else jnp.float32
        lhs = jnp.asarray(rng.normal(size=(T, K)), dt)
        rhs = jnp.asarray(rng.normal(size=(E, K, N)), dt)
        be = jnp.asarray(np.sort(rng.integers(0, E, size=T // bt)), jnp.int32)
        cands = candidates or ([{"block_k": bk, "block_n": bn}
                                for bk in (256, 512) for bn in (256, 512)]
                               if on_tpu else
                               [{"block_k": 32, "block_n": 32}, {"block_k": 64, "block_n": 64}])

        def build(c):
            return lambda: gmm(lhs, rhs, be, block_t=bt, block_k=c["block_k"],
                               block_n=c["block_n"], interpret=not on_tpu)

        return self.sweep("grouped_matmul", shape_bucket(K=K, N=N), cands, build)

    def tune_all(self, kernels: Sequence[str] = ("flash_attention", "paged_attention",
                                                 "paged_decode", "grouped_matmul")) -> str:
        """Run every requested sweep, persist ``kernel_config.json`` into
        ``output_dir`` (next to the config sweep's ``best_config.json``) and
        return the artifact path."""
        if "flash_attention" in kernels:
            self.tune_flash()
        if "paged_attention" in kernels:
            self.tune_paged()
        if "paged_decode" in kernels:
            self.tune_paged_decode()
        if "grouped_matmul" in kernels:
            self.tune_grouped()
        path = self.registry.save(os.path.join(self.output_dir, CONFIG_FILENAME))
        logger.info(f"kernel autotune: wrote {path} "
                    f"({sum(len(v) for v in self.results.values())} tuned buckets)")
        return path
