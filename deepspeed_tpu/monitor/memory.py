"""Engine-wide HBM capacity attribution.

The serving and training engines each know what THEY put on the device
(params, optimizer/ZeRO shards, the KV block pool, a speculative draft
engine), but nobody could answer "what fraction of HBM is params vs KV pool
vs cold cache" without reading five subsystems — the question every
capacity decision (ROADMAP items 1/2/4: KV spill pool sizing, disaggregated
pools, multi-tenant packing) starts from. This module is the one ledger:

  * components register byte providers at construction
    (:meth:`MemoryAttribution.register`: ``fn(owner) -> {section: bytes}``,
    owner held by WEAK reference so a discarded engine never leaks through
    telemetry — dead providers are pruned at the next report);
  * :func:`hbm_report` folds every live provider into a section
    decomposition (``params`` / ``optimizer`` / ``kv_block_pool`` /
    ``spec_draft_engine`` / ...), reconciled against
    ``jax.local_devices()`` memory stats where the backend exposes them
    (TPU; CPU reports null device stats, never a made-up number) — the
    remainder shows up as ``unattributed_bytes`` ("other": XLA temp
    buffers, compiled executables, anything not yet registered;

and three export paths, all existing PR 1/5 surfaces: the health exporter
renders :meth:`MemoryAttribution.gauge_rows` as labelled
``memory/hbm_bytes{section=...}`` gauges on ``/metrics``, every forensic
stall dump gains a ``memory`` section (registered by
``HealthPlane.configure``), and :func:`hbm_report` returns the same report
as one dict.

Import-light (stdlib only at module level; jax imported lazily per report).
"""

import threading
import weakref


def tree_device_bytes(tree) -> int:
    """Bytes the array leaves of a pytree occupy on THIS HOST's devices.

    Sharded jax arrays are summed over their addressable shards — the same
    denominator ``device_memory_stats`` reports — so a ZeRO-3 param tree on
    an N-host pod attributes one host's shard bytes, not N× the global
    logical size (and a replicated array counts once per local device
    holding a copy, exactly as the backend's ``bytes_in_use`` does). Host
    numpy arrays and anything else exposing ``nbytes`` fall back to their
    full size; non-array leaves count zero."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is not None:
            try:
                total += sum(int(s.data.nbytes) for s in shards)
                continue
            except Exception:
                pass
        total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


def device_memory_stats():
    """Per-host device memory stats summed over ``jax.local_devices()``:
    ``{bytes_in_use, bytes_limit, peak_bytes_in_use, n_devices}`` — or None
    when the backend exposes none (CPU), so callers report null rather than
    inventing a denominator."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return None
    agg = {"bytes_in_use": 0, "bytes_limit": 0, "peak_bytes_in_use": 0,
           "n_devices": 0}
    seen = False
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats or "bytes_in_use" not in stats:
            continue
        seen = True
        agg["n_devices"] += 1
        agg["bytes_in_use"] += int(stats.get("bytes_in_use", 0))
        agg["bytes_limit"] += int(stats.get("bytes_limit", 0))
        agg["peak_bytes_in_use"] += int(stats.get("peak_bytes_in_use", 0))
    return agg if seen else None


class MemoryAttribution:
    """Process-global provider registry (see :func:`get_memory`)."""

    def __init__(self):
        self._lock = threading.Lock()
        # name -> (weakref(owner), fn); fn(owner) -> {section: bytes}
        self._providers = {}

    def register(self, name, fn, owner) -> None:
        """Register ``fn(owner) -> {section: bytes}`` under a unique
        ``name``. ``owner`` is weakly referenced: when it is collected the
        provider self-prunes — engines without a destroy() (the serving
        engine) can register fire-and-forget."""
        with self._lock:
            self._providers[name] = (weakref.ref(owner), fn)

    def unregister(self, name) -> None:
        with self._lock:
            self._providers.pop(name, None)

    def sections(self):
        """Live section decomposition: bytes summed per section across every
        provider whose owner is still alive (dead ones pruned here)."""
        with self._lock:
            items = list(self._providers.items())
        out = {}
        dead = []
        for name, (ref, fn) in items:
            owner = ref()
            if owner is None:
                dead.append(name)
                continue
            try:
                for section, nbytes in fn(owner).items():
                    out[section] = out.get(section, 0) + int(nbytes)
            except Exception:  # a broken provider costs its rows, never the report
                continue
        if dead:
            with self._lock:
                for name in dead:
                    self._providers.pop(name, None)
        return out

    def report(self) -> dict:
        """The full attribution: per-section bytes, the accounted total, the
        backend's own in-use/limit numbers where available, and the
        unattributed remainder (XLA temporaries, executables, anything not
        registered — the honest "other")."""
        sections = self.sections()
        accounted = sum(sections.values())
        device = device_memory_stats()
        out = {"sections": sections, "accounted_bytes": accounted,
               "device": device, "unattributed_bytes": None}
        if device is not None:
            out["unattributed_bytes"] = max(0, device["bytes_in_use"] - accounted)
        return out

    def gauge_rows(self):
        """Labelled gauges for the health exporter's ``/metrics``."""
        rows = [("memory/hbm_bytes", {"section": s}, v)
                for s, v in sorted(self.sections().items())]
        device = device_memory_stats()
        if device is not None:
            rows.append(("memory/device_bytes_in_use", {}, device["bytes_in_use"]))
            rows.append(("memory/device_bytes_limit", {}, device["bytes_limit"]))
        return rows


_memory = MemoryAttribution()


def get_memory() -> MemoryAttribution:
    return _memory


def hbm_report() -> dict:
    """Module-level convenience: the current process-wide HBM attribution
    (what every forensic dump carries)."""
    return _memory.report()
