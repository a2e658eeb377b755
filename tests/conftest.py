"""Test harness.

The reference spawns N torch processes per test (``tests/unit/common.py:324``
``DistributedTest``). The TPU-native analog (SURVEY.md §4 takeaway): a single
process with N virtual devices — ``xla_force_host_platform_device_count`` —
so every mesh/sharding/collective path runs exactly as it would on an N-chip
slice, minus the ICI. Env vars must be set before jax import.
"""

import atexit
import faulthandler
import os
import shutil
import signal
import sys
import tempfile

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
# XLA:CPU compiles what these tests run at its lowest optimization level (jax's
# own flag: LLVM at -O0 and none of its expensive passes). The programs are
# tiny and run once or twice, so most of a test's CPU time is compiling, on as
# many threads as the machine has cores, beside five other workers. The same
# tree, back to back on one sandbox, took 1,608 s with the optimizations (the
# driver cuts the run at 1,470 s) and 1,114 s without: 9,059 against 6,196 s
# of tests (PERF.md section 2, "How a PR is checked"). Set in ``os.environ``
# before jax is imported, so the rehearsals and drills that tests start as
# processes of their own compile the same way: a quarter of the suite's time
# is theirs. Nothing outside ``tests/`` sets it: no run of the benchmark and
# no run on the chip sees it.
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")

import jax  # noqa: E402

assert len(jax.devices()) >= 8, f"expected 8 virtual CPU devices, got {jax.devices()}"
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax._src import compiler as _jax_compiler  # noqa: E402
from jax.experimental.compilation_cache import compilation_cache  # noqa: E402

# Each distinct program is compiled once a test process. Every engine keeps
# ``jax.jit`` objects of its own, so a file that builds an engine a test would
# otherwise compile the same step programs again and again. The directory is
# this PROCESS's (an xdist worker, or the one process of a serial run), empty
# at every start and removed when the session or the process ends; it is set in ``jax.config`` and not in
# ``os.environ``, so the sub-processes tests start choose theirs as before.
_CACHE_DIR = tempfile.mkdtemp(prefix="dstpu_tests_compiled_")
_MIN_COMPILE_S = 0.3  # a program that compiled faster is not kept
atexit.register(shutil.rmtree, _CACHE_DIR, ignore_errors=True)
jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", _MIN_COMPILE_S)

# A program over several devices is never an entry. Loaded back from the cache,
# the collectives of such a program meet out of step in this installation's
# XLA:CPU (jaxlib 0.9.0: one device waits at ``op_id=29``, seven at ``op_id=1``),
# and after 40 s the runtime ABORTS the process, a worker with every test it
# still held (``test_model_families.py`` and ``test_engine_zero.py``, run again
# over a kept directory). ``None`` is jax's own word for "compile without the
# cache"; one-device programs, the serving engines', load back sound.
_cache_key = _jax_compiler._get_cache_key


def _cache_key_of_a_one_device_program(options, backend, computation, devices, override_fdo_profile=None):
    if devices.size > 1:
        return None
    return _cache_key(options, backend, computation, devices, override_fdo_profile)


_jax_compiler._get_cache_key = _cache_key_of_a_one_device_program

# The files that go out first, longest first: ``--dist loadfile`` keeps a file
# on one worker, so a long file that starts late is the run's tail (a file of
# 138 worker-seconds that began at 1,020 s of 1,159 left four workers of six
# idle for 90 s). Every file over 100 worker-seconds in PR 42's run, the
# sub-process files of ``tests/perfbench/`` among them; a new file of few long
# tests is added here (PERF.md section 2, "How a PR is checked").
_FIRST_OUT = (
    "perfbench/test_bench_rehearsal.py",
    "test_kernel_tuning.py",
    "perfbench/test_bench_host_gaps.py",
    "test_glm.py",
    "test_engine_zero.py",
    "test_trinity.py",
    "test_minicpm.py",
    "test_nemotron.py",
    "test_sdar.py",
    "perfbench/test_bench_sdar.py",
    "perfbench/test_bench_nemotron.py",
    "test_checkpoint_tools.py",
    "test_program_spans.py",
    "test_inference_v2.py",
    "test_autotuning_compression.py",
    "test_resilience_chaos.py",
    "test_resilience.py",
    "perfbench/test_bench_kv_live.py",
    "test_offload.py",
    "test_ops.py",
    "test_aux_components.py",
    "test_pipeline.py",
    "test_speculative.py",
    "test_mellum.py",
    "test_tiled_blocks_per_step.py",
)

# A test's own limit, in seconds. One that nears it is repaired, not given more.
_TEST_LIMIT_S = 300


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from tier-1 (-m 'not slow')")
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False  # xdist: not by number of tests, by _FIRST_OUT


def pytest_unconfigure(config):
    # an xdist worker that is slow to exit is killed, and ``atexit`` with it
    shutil.rmtree(_CACHE_DIR, ignore_errors=True)


def pytest_addoption(parser):
    parser.addoption("--smoke", action="store_true", default=False,
                     help="run only the ~5-minute smoke subset (tests/smoke.txt): "
                          "one fast representative per subsystem, for quick CI "
                          "iteration — the full suite remains the merge gate")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--smoke"):
        smoke_file = os.path.join(os.path.dirname(__file__), "smoke.txt")
        pats = [ln.strip() for ln in open(smoke_file)
                if ln.strip() and not ln.startswith("#")]
        keep, drop = [], []
        for item in items:
            (keep if any(p in item.nodeid for p in pats) else drop).append(item)
        assert keep, "smoke.txt matched no tests — stale patterns?"
        config.hook.pytest_deselected(items=drop)
        items[:] = keep
    here = os.path.dirname(__file__)
    rank = {os.path.join(here, name): i for i, name in enumerate(_FIRST_OUT)}
    items.sort(key=lambda item: rank.get(str(item.path), len(rank)))


@pytest.fixture(autouse=True)
def _reset_groups():
    from deepspeed_tpu.parallel import groups

    groups.reset()
    yield
    groups.reset()


@pytest.fixture(autouse=True)
def _test_limit(request):
    """Fail a test that runs past ``_TEST_LIMIT_S`` by name, with every
    thread's stack on stderr, instead of letting it take the run's clock."""
    if not hasattr(signal, "SIGALRM"):
        yield
        return

    def expired(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        pytest.fail(f"{request.node.nodeid} ran past its limit of {_TEST_LIMIT_S} s", pytrace=False)

    before = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, _TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture
def cold_compile():
    """For a test that is about compiling: no cache directory while it runs,
    so every program it builds is compiled (a hit gives no compile event and
    prints the CPU loader's lines on stderr). The installed jax keeps its open
    cache object across a change of the directory, so it is reset both ways."""
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    compilation_cache.reset_cache()


@pytest.fixture
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs


def tiny_batch(batch_size=8, seq=32, vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, size=(batch_size, seq), dtype=np.int32)}
