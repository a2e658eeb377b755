"""deepspeed_tpu.comm — the torch.distributed-compatible API surface.

Analog of the reference ``deepspeed/comm/comm.py`` (contract stated at lines
13-19: mirror torch.distributed signatures). Two planes:

  * Host plane (this module): ``init_distributed`` (reference :604),
    ``get_rank``/``get_world_size`` (:530-564), ``barrier`` (:405) —
    process-level bootstrap and control, backed by ``XlaBackend``.
  * Traced plane (``comm.functional`` re-exported here): ``all_reduce``,
    ``all_gather``, ``reduce_scatter``, ``all_to_all_single`` etc. that compile
    into step programs over mesh axes.

The global backend handle is ``cdb`` — same name as reference ``comm.py:41``.
"""

import inspect
import os
import threading
import time
import functools

from .backend import XlaBackend
from . import functional as _functional
from .functional import ReduceOp, axis_index, axis_size  # noqa: F401 — pure helpers, no comm payload
from ..monitor.trace import get_tracer
from ..runtime.resilience import chaos
from ..utils.logging import logger, log_dist
from ..utils.comms_logging import CommsLogger, calc_bw_log

cdb = None
comms_logger = CommsLogger()
timers = None


class CommException(Exception):
    pass


# ---------------------------------------------------------------------------
# instrumentation: real message sizes, wall times, trace spans
# ---------------------------------------------------------------------------
def _leaf_nbytes(x):
    """Bytes carried by one pytree leaf: concrete arrays via ``nbytes``,
    tracers via their aval shape/dtype, non-tensor leaves count zero."""
    import numpy as np

    nb = getattr(x, "nbytes", None)
    if isinstance(nb, (int, np.integer)):
        return int(nb)
    aval = getattr(x, "aval", None)
    if aval is not None and hasattr(aval, "shape") and hasattr(aval, "dtype"):
        try:
            return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
        except Exception:
            return 0
    return 0


def _msg_bytes(args, kwargs):
    """Pytree-aware payload size: the nbytes sum over every tensor leaf in
    the call (the reference sizes ``tensor.element_size() * tensor.nelement()``;
    here a collective may carry a whole tree)."""
    import jax

    return sum(_leaf_nbytes(l) for l in jax.tree_util.tree_leaves((args, kwargs)))


def _has_tracer(args, kwargs):
    import jax

    return any(isinstance(l, jax.core.Tracer) for l in jax.tree_util.tree_leaves((args, kwargs)))


def _group_degree(group):
    """Participant count of a collective over ``group`` — the ``n`` in the
    algbw/busbw formulas. Mesh-axis groups use the axis extent (devices);
    rank-list groups their length; fallback is the process world size."""
    try:
        from ..parallel import groups as pgroups

        if pgroups.is_initialized():
            mesh = pgroups.get_mesh()
            if group is None:
                return max(1, mesh.size)
            names = group if isinstance(group, (list, tuple)) else (group, )
            if all(isinstance(a, str) and a in mesh.shape for a in names):
                d = 1
                for a in names:
                    d *= mesh.shape[a]
                return max(1, d)
    except Exception:
        pass
    if isinstance(group, (list, tuple)) and group and all(isinstance(r, int) for r in group):
        return len(group)
    if cdb is not None:
        return max(1, cdb.get_world_size())
    return 1


def _block_on(result):
    """Drain async dispatch so the wall time covers the transfer, giving the
    same 'device work up to here is done' point CUDA events give the
    reference's timed_op."""
    try:
        import jax

        jax.block_until_ready(result)
    except Exception:
        pass
    return result


class _InflightCollectives:
    """Registry of collectives currently executing on this host — the table
    the health plane (``monitor/health.py``) dumps when a run wedges: a hung
    all-reduce is invisible from outside the process, but THIS table names
    the op, its payload size, how long it has been in flight, and which
    thread sits in it. Fed by ``@timed_op`` (device collectives) and the
    host-plane gather/broadcast helpers. Disabled by default: one attribute
    check per call, no locking, no allocations — the health config block
    flips ``enabled`` and installs the ``on_enter``/``on_exit`` heartbeat
    hooks (the ``collective`` stall-watchdog source)."""

    __slots__ = ("enabled", "on_enter", "on_exit", "_lock", "_entries", "_next")

    def __init__(self):
        self.enabled = False
        self.on_enter = None  # health hook: begin("collective")
        self.on_exit = None  # health hook: end("collective")
        self._lock = threading.Lock()
        self._entries = {}
        self._next = 0

    def enter(self, op, msg_size=0):
        """Register an in-flight collective; returns the token for exit()."""
        with self._lock:
            token = self._next
            self._next += 1
            self._entries[token] = {"op": op, "msg_size": int(msg_size),
                                    "t0": time.perf_counter(),
                                    "thread": threading.current_thread().name}
        cb = self.on_enter
        if cb is not None:
            cb()
        return token

    def exit(self, token):
        with self._lock:
            self._entries.pop(token, None)
        cb = self.on_exit
        if cb is not None:
            cb()

    def snapshot(self):
        """Ordered view of the table: ``[{op, msg_size, age_s, thread}]``,
        oldest first."""
        now = time.perf_counter()
        with self._lock:
            entries = sorted(self._entries.items())
        return [{"op": e["op"], "msg_size": e["msg_size"],
                 "age_s": round(now - e["t0"], 4), "thread": e["thread"]}
                for _, e in entries]

    def __len__(self):
        return len(self._entries)


inflight_collectives = _InflightCollectives()


def timed_op(func):
    """Reference ``comm.py:101`` @timed_op — wall-times collectives with REAL
    payload bytes (pytree nbytes sum, not the old hardcoded 0).

    Three regimes:
      * profiling off (default): straight call — zero overhead;
      * under jit (tracer args): the collective compiles into the step
        program, so host wall time is meaningless — record an instant trace
        event carrying the traced payload size;
      * eager concrete call: wall-time around a ``block_until_ready`` and
        feed latency + bytes through ``calc_bw_log`` (comms logger + a
        ``comm/<op>`` trace span with algo/bus bandwidth).
    """
    name = func.__name__
    try:
        sig = inspect.signature(func)
        group_default = sig.parameters["group"].default if "group" in sig.parameters else None
    except (TypeError, ValueError):
        sig, group_default = None, None

    def _call_group(args, kwargs):
        """The group actually in effect — positional, keyword or default."""
        if sig is not None:
            try:
                return sig.bind(*args, **kwargs).arguments.get("group", group_default)
            except TypeError:
                pass
        return kwargs.get("group", group_default)

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        tracer = get_tracer()
        watch = inflight_collectives
        prof = comms_logger.enabled and (comms_logger.prof_all or name in comms_logger.prof_ops)
        timing = prof or tracer.enabled
        chaotic = chaos.armed("comm/collective")
        if not (timing or watch.enabled or chaotic):
            return func(*args, **kwargs)
        if _has_tracer(args, kwargs):
            # under jit the call only records into the step program: nothing
            # can block here, so it is neither timed nor held in flight (and
            # a chaos delay/kill here would poison the compile, not the
            # transfer — the chaos bracket covers CONCRETE calls only)
            if tracer.enabled:
                tracer.instant(f"comm/{name}", tid="comm",
                               msg_size=_msg_bytes(args, kwargs), traced=True)
            return func(*args, **kwargs)
        msg_size = _msg_bytes(args, kwargs)
        chaos.fire("comm/collective", {"op": name})
        token = watch.enter(name, msg_size) if watch.enabled else None
        try:
            if not timing:
                # watch-only mode (health plane armed, profiling off): the
                # in-flight entry brackets the call with NO forced device
                # sync — eager dispatch keeps its async perf profile
                return func(*args, **kwargs)
            n = _group_degree(_call_group(args, kwargs))
            _eager_state["compiled"] = False
            t0 = time.perf_counter()
            result = _block_on(func(*args, **kwargs))
            duration = time.perf_counter() - t0
            compiled = _eager_state["compiled"]
            if prof and not compiled:
                # a call that just compiled its eager executable is not a
                # steady-state sample — keep it out of the bandwidth stats
                comms_logger.append(name, name, duration, msg_size, n=n)
            if tracer.enabled:
                algbw, busbw, _ = calc_bw_log(name, msg_size, duration, n=n)
                span_args = {"msg_size": msg_size, "algbw_gbps": round(algbw, 4),
                             "busbw_gbps": round(busbw, 4), "n": n}
                if compiled:
                    span_args["compiled"] = True  # disclosed, excluded from stats
                tracer.complete(f"comm/{name}", t0, duration, tid="comm", args=span_args)
            return result
        finally:
            if token is not None:
                watch.exit(token)

    return wrapper


# eager-executable subset: replicated-operand semantics are well defined for
# these (the result every participant agrees on); all_to_all and the ring/p2p
# ops have inherently per-participant results and stay jit-only
_EAGER_OK = frozenset({
    "all_reduce", "inference_all_reduce", "all_gather", "reduce_scatter", "broadcast"
})

# signal from _eagerize to timed_op: the call it just serviced compiled a new
# executable, so its wall time is NOT a steady-state comm sample
_eager_state = {"compiled": False}
_EAGER_CACHE_MAX = 64  # per-op bound; entries pin their mesh + executable


def _eager_out_spec(name, axes, bound_args):
    from jax.sharding import PartitionSpec as P

    if name == "reduce_scatter":
        dim = bound_args.get("scatter_dimension", 0)
        return P(*([None] * dim + [tuple(axes) if len(axes) > 1 else axes[0]]))
    return P()


def _eagerize(func):
    """Let a traced-plane collective run with CONCRETE arrays outside jit:
    the call is wrapped in a one-off ``shard_map`` over the current mesh
    (operands replicated), jitted, executed and cached by shape — the
    torch.distributed ergonomics, and what lets ``timed_op`` wall-time a real
    device collective (the tracer's ``comm/*`` spans). Inside jit, or
    with no mesh initialized, the call passes through untouched.

    Caveat: the FIRST eager call per (op, shape, dtype, group) includes the
    jit compile in its wall time — discard or warm past that sample when
    deriving steady-state bandwidth."""
    name = func.__name__
    sig = inspect.signature(func)
    cache = {}

    tensor_param = next(iter(sig.parameters))  # the payload is always first

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        if name in _EAGER_OK and (args or kwargs) and not _has_tracer(args, kwargs):
            try:
                from ..parallel import groups as pgroups

                eligible = pgroups.is_initialized()
                rest = tensor_val = None
                if eligible:
                    try:
                        bound = sig.bind(*args, **kwargs)
                    except TypeError:
                        eligible = False  # malformed call: let func raise its own error
                if eligible:
                    bound.apply_defaults()
                    rest = dict(bound.arguments)
                    tensor_val = rest.pop(tensor_param, None)
                    group = rest.get("group")
                    mesh = pgroups.get_mesh()
                    axes = group if isinstance(group, (list, tuple)) else (group, )
                    eligible = tensor_val is not None and \
                        all(isinstance(a, str) and a in mesh.shape for a in axes)
                if eligible:
                    import jax
                    import jax.numpy as jnp
                    from jax.sharding import PartitionSpec as P

                    tensor = jnp.asarray(tensor_val)
                    key = (name, tensor.shape, str(tensor.dtype), tuple(axes),
                           repr(sorted((k, repr(v)) for k, v in rest.items())), id(mesh))
                    fn = cache.get(key)
                    if fn is None:
                        from ..parallel.mesh import shard_map_compat

                        out_spec = _eager_out_spec(name, tuple(axes), bound.arguments)
                        inner = lambda x, _rest=rest: func(x, **_rest)
                        fn = jax.jit(shard_map_compat(inner, mesh, P(), out_spec))
                        while len(cache) >= _EAGER_CACHE_MAX:  # FIFO bound:
                            cache.pop(next(iter(cache)))  # entries pin meshes
                        cache[key] = fn
                        _eager_state["compiled"] = True
                    with mesh:
                        return fn(tensor)
            except Exception as e:
                raise CommException(
                    f"eager {name} over mesh failed ({type(e).__name__}: {e}); call it inside "
                    "jit/shard_map over the target axis for full control") from e
        # in-jit, no mesh, or non-eagerable op: the traced plane as before
        return func(*args, **kwargs)

    return wrapper


# ---------------------------------------------------------------------------
# public traced-plane surface: EVERY collective rides @timed_op (the static
# check tools/check_timed_ops.py keeps this from rotting)
# ---------------------------------------------------------------------------
all_reduce = timed_op(_eagerize(_functional.all_reduce))
inference_all_reduce = timed_op(_eagerize(_functional.inference_all_reduce))
all_gather = timed_op(_eagerize(_functional.all_gather))
all_gather_into_tensor = all_gather  # alias parity with the functional plane
reduce_scatter = timed_op(_eagerize(_functional.reduce_scatter))
reduce_scatter_tensor = reduce_scatter
all_to_all_single = timed_op(_eagerize(_functional.all_to_all_single))
broadcast = timed_op(_eagerize(_functional.broadcast))
ppermute = timed_op(_functional.ppermute)
send_recv_next = timed_op(_functional.send_recv_next)
send_recv_prev = timed_op(_functional.send_recv_prev)
send = timed_op(_functional.send)
recv = timed_op(_functional.recv)


@timed_op
def zero3_params_allgather(params, specs=None, mesh=None, group=None):
    """Explicit ZeRO-3 per-layer parameter all-gather (the
    ``zero_optimization.overlap_comm`` schedule — ``models/transformer.py``
    issues layer *l+1*'s gather during layer *l*'s compute).

    In GSPMD form the gather IS a sharding constraint: each leaf is pinned to
    its gathered compute layout (TP spec with the ZeRO data axes dropped) and
    XLA lowers the boundary to the all-gather. Riding ``@timed_op`` puts the
    prefetch on the same observability surface as every other collective:
    under jit a ``comm/zero3_params_allgather`` instant (with real payload
    bytes) lands on the trace bus per compile, and eager executions bracket
    the PR 5 ``_InflightCollectives`` table / heartbeat hooks.

    ``specs``: dict leaf-name -> PartitionSpec (None entries skipped, e.g.
    expert-parallel weights whose data-axis sharding is EP, not ZeRO).
    No mesh/specs (CPU tests, no registry) -> identity.
    """
    if mesh is None or specs is None:
        return params
    import jax
    from jax.sharding import NamedSharding

    out = {}
    for k, v in params.items():
        s = specs.get(k)
        out[k] = v if s is None else jax.lax.with_sharding_constraint(v, NamedSharding(mesh, s))
    return out


def init_distributed(dist_backend="xla",
                     auto_mpi_discovery=True,
                     distributed_port=29500,
                     verbose=True,
                     timeout=None,
                     init_method=None,
                     dist_init_required=None,
                     config=None,
                     rank=-1,
                     world_size=-1):
    """Initialize the distributed runtime (reference ``comm.py:604``).

    On TPU this (a) optionally runs MPI/env rank discovery (reference
    :650-658 ``mpi_discovery``), (b) initializes ``jax.distributed`` when a
    coordinator is configured, and (c) installs the global ``cdb`` backend.
    Collectives themselves need no process groups — they compile into step
    programs over the mesh.
    """
    global cdb
    if cdb is not None and cdb.is_initialized():
        return cdb

    if auto_mpi_discovery and not _env_ranks_present() and _in_mpi_environment():
        mpi_discovery(distributed_port=distributed_port, verbose=verbose)

    cdb = XlaBackend(init_method=init_method, rank=rank, world_size=world_size)
    if verbose:
        log_dist(f"initialized comm backend '{dist_backend}' rank={cdb.get_rank()} "
                 f"world_size={cdb.get_world_size()}", ranks=[0])
    if config is not None:
        configure(config)
    return cdb


def _env_ranks_present():
    return all(v in os.environ for v in ("RANK", "WORLD_SIZE"))


def _in_mpi_environment():
    return any(v in os.environ for v in ("OMPI_COMM_WORLD_RANK", "PMI_RANK", "SLURM_PROCID"))


def mpi_discovery(distributed_port=29500, verbose=True):
    """Rank discovery from MPI/SLURM env (reference ``comm.py:673-771``)."""
    if "OMPI_COMM_WORLD_RANK" in os.environ:
        rank = int(os.environ["OMPI_COMM_WORLD_RANK"])
        world_size = int(os.environ["OMPI_COMM_WORLD_SIZE"])
        local_rank = int(os.environ.get("OMPI_COMM_WORLD_LOCAL_RANK", 0))
    elif "SLURM_PROCID" in os.environ:
        rank = int(os.environ["SLURM_PROCID"])
        world_size = int(os.environ.get("SLURM_NTASKS", 1))
        local_rank = int(os.environ.get("SLURM_LOCALID", 0))
    else:
        rank = int(os.environ.get("PMI_RANK", 0))
        world_size = int(os.environ.get("PMI_SIZE", 1))
        local_rank = 0
    os.environ.setdefault("RANK", str(rank))
    os.environ.setdefault("WORLD_SIZE", str(world_size))
    os.environ.setdefault("LOCAL_RANK", str(local_rank))
    from ..launcher.constants import ENV_COORDINATOR_ADDRESS

    if "MASTER_ADDR" in os.environ and ENV_COORDINATOR_ADDRESS not in os.environ:
        os.environ[ENV_COORDINATOR_ADDRESS] = f"{os.environ['MASTER_ADDR']}:{distributed_port}"
    if verbose:
        logger.info(f"mpi_discovery: rank={rank} world_size={world_size} local_rank={local_rank}")


def is_initialized():
    return cdb is not None and cdb.is_initialized()


def _ensure():
    global cdb
    if cdb is None:
        init_distributed()
    return cdb


def get_rank(group=None):
    return _ensure().get_rank()


def get_world_size(group=None):
    return _ensure().get_world_size()


def get_local_rank():
    return int(os.environ.get("LOCAL_RANK", 0))


@timed_op
def barrier(group=None):
    _ensure().barrier()


# goodput's exposed-comm feed: fn(op, seconds) set by monitor/goodput.py
# while the ledger is armed (None = one global read + branch per host op).
# The host-plane collectives already BLOCK the caller, so timing them here
# adds no sync the call wasn't paying.
goodput_comm_hook = None


def _watched_host_op(op, fn):
    """Host-plane collectives (key-value-store gather/broadcast) BLOCK the
    calling thread until every process arrives — they are the ops a dead
    peer wedges first (the step-boundary resilience vote rides
    ``all_gather_host``). Register them in the in-flight table while the
    health plane watches."""
    # chaos bracket: collective-delay/kill storms land on the host plane
    # here — these are the blocking ops a dead peer wedges first
    hook = goodput_comm_hook
    t0 = time.perf_counter() if hook is not None else 0.0
    try:
        chaos.fire("comm/host_collective", {"op": op})
        watch = inflight_collectives
        if not watch.enabled:
            return fn()
        token = watch.enter(op)
        try:
            return fn()
        finally:
            watch.exit(token)
    finally:
        if hook is not None:
            hook(op, time.perf_counter() - t0)


def broadcast_object_list(object_list, src=0, group=None):
    out = _watched_host_op("broadcast_object_list",
                           lambda: _ensure().broadcast_host(object_list, src=src))
    object_list[:] = list(out) if not isinstance(out, list) else out
    return object_list


def broadcast_host(value, src=0):
    return _watched_host_op("broadcast_host",
                            lambda: _ensure().broadcast_host(value, src=src))


def all_gather_host(value):
    return _watched_host_op("all_gather_host",
                            lambda: _ensure().all_gather_host(value))


def new_group(ranks=None):
    """Groups are mesh axes on TPU; host-plane subgroup creation is a no-op
    returning the rank list for API compatibility (reference ``comm.py:181``)."""
    return tuple(ranks) if ranks is not None else None


def destroy_process_group(group=None):
    global cdb
    if cdb is not None:
        cdb.destroy_process_group()
        cdb = None


def configure(config=None, deepspeed_config=None, enabled=None, prof_all=None, prof_ops=None, verbose=None, debug=None):
    cfg = config or deepspeed_config
    if cfg is not None and getattr(cfg, "comms_config", None) is not None:
        comms_logger.configure(cfg.comms_config)
    if enabled is not None:
        comms_logger.enabled = enabled
    if prof_all is not None:
        comms_logger.prof_all = prof_all
    if prof_ops is not None:
        comms_logger.prof_ops = prof_ops
    if verbose is not None:
        comms_logger.verbose = verbose
    if debug is not None:
        comms_logger.debug = debug


def log_summary(show_straggler=False):
    """Print the comms profile (reference ``comm.py:422``)."""
    return comms_logger.log_all(print_log=(get_rank() == 0), show_straggler=show_straggler)


# ---------------------------------------------------------------------------
# reference comm.py surface parity — host-level introspection & environment
# ---------------------------------------------------------------------------
def is_available() -> bool:
    """Reference ``is_available``: the XLA backend ships with jax."""
    return True


def get_world_group():
    """Reference ``get_world_group``: None IS the world group in this API
    (every op treats group=None as all processes)."""
    return None


def get_global_rank(group=None, group_rank: int = 0) -> int:
    """Reference ``get_global_rank``: groups here are mesh-axis names whose
    members enumerate in world order, so a group-local rank maps through the
    group's rank list."""
    ranks = get_all_ranks_from_group(group)
    return ranks[group_rank]


def get_all_ranks_from_group(group=None):
    """Reference helper of the same name. For a mesh-axis-name group the
    ranks are DEVICE ids (one process owns many devices here): the group is
    the set of devices varying along that axis with this process's first
    addressable device's other coordinates held fixed — the device-level
    analog of "the subgroup containing my rank"."""
    if group is None:
        return list(range(get_world_size()))
    if isinstance(group, (list, tuple)) and all(isinstance(r, int) for r in group):
        return list(group)
    if isinstance(group, str):
        from ..parallel import groups as pgroups

        if pgroups.is_initialized():
            import jax
            import numpy as np

            mesh = pgroups.get_mesh()
            if group in mesh.axis_names:
                ids = np.vectorize(lambda d: d.id)(mesh.devices)
                ax = mesh.axis_names.index(group)
                my = jax.local_devices()[0].id
                pos = np.argwhere(ids == my)
                if pos.size:
                    idx = list(pos[0])
                    idx[ax] = slice(None)
                    return sorted(int(x) for x in np.ravel(ids[tuple(idx)]))
    return list(range(get_world_size()))


def monitored_barrier(group=None, timeout=None, wait_all_ranks: bool = False):
    """Reference ``monitored_barrier``: barrier + a log line (the jax
    coordination service already detects/reports stragglers by timeout)."""
    from ..utils.logging import logger

    t0 = time.time()
    barrier(group)
    dt = time.time() - t0
    if timeout is not None and dt > float(timeout):
        logger.warning(f"monitored_barrier took {dt:.1f}s (> {timeout})")
    return None


def set_backend(backend_name: str = "xla"):
    """Reference ``set_backend``: only the XLA backend exists here."""
    if backend_name not in ("xla", "hccl", "nccl", "ccl"):
        raise ValueError(f"unknown backend {backend_name!r}")
    return None


def init_deepspeed_backend(ds_backend=None, timeout=None, init_method=None, rank=-1, world_size=-1):
    """Reference ``init_deepspeed_backend``: folded into init_distributed."""
    return None


def in_aml() -> bool:
    """Azure ML env detection (reference comm.py)."""
    return "AZUREML_EXPERIMENT_ID" in os.environ


def in_aws_sm() -> bool:
    return "SM_TRAINING_ENV" in os.environ


def in_dlts() -> bool:
    return "DLTS_JOB_ID" in os.environ
