"""The harness's own rules (``tests/conftest.py``): each distinct program is
compiled once a test process, the long files go out first, every test has a
limit of its own."""

import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest
from deepspeed_tpu.inference.v2 import DSStateManagerConfig, InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.models import llama2

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)


def _entries():
    return {name for name in os.listdir(conftest._CACHE_DIR) if name.endswith("-cache")}


def test_the_cache_directory_is_this_processes_own_and_set_in_jax_config_alone():
    """Under the temporary directory, not the checkout's ``.jax_cache`` (which
    the programs a test starts as sub-processes fall back to), and nothing in
    the environment a sub-process inherits says where it is."""
    path = jax.config.jax_compilation_cache_dir
    assert path == conftest._CACHE_DIR and os.path.isdir(path)
    assert os.path.dirname(path) == tempfile.gettempdir()
    assert path != os.path.join(REPO, ".jax_cache")
    assert path not in os.environ.values(), "conftest.py exports no cache directory"
    assert jax.config.jax_persistent_cache_min_compile_time_secs == conftest._MIN_COMPILE_S


def _put_through_a_new_engine():
    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2, intermediate_size=128,
                   vocab_size=160, max_seq_len=256, dtype=jnp.float32, attention_impl="reference")
    sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64, max_ragged_sequence_count=4,
                              max_context=64)
    engine = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        kv_block_size=8, num_kv_blocks=32, kv_dtype=jnp.float32, state_manager=sm, use_pallas_kernels="never"))
    return np.asarray(engine.put([3], [np.arange(1, 20, dtype=np.int32)]))


def test_a_second_engine_built_alike_compiles_nothing(keep_every_program):
    """Every engine has ``jax.jit`` objects of its own: the first build's
    programs are entries of the directory, the second build finds them. The
    threshold is zero for this test, so that which programs are kept does not
    hang on how long this machine took to compile each."""
    before = _entries()
    first = _put_through_a_new_engine()
    after_first = _entries()
    second = _put_through_a_new_engine()
    after_second = _entries()
    assert len(after_first) > len(before), "the first engine's step program is kept"
    assert after_second == after_first, sorted(after_second - after_first)
    np.testing.assert_array_equal(first, second)


def _a_program_of_this_files_own():
    """A new function each call, so that no cache in memory knows it."""
    return jax.jit(lambda x: jnp.tanh(x) @ x.T * 1.25)


@pytest.fixture
def keep_every_program():
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", conftest._MIN_COMPILE_S)


@pytest.fixture
def after_cold_compile(keep_every_program):
    """Set up before ``cold_compile``, so torn down after it: the old directory
    is back, with what it held, and in use: the program that left no entry
    inside the fixture is compiled again and leaves one."""
    held = _entries()
    yield held
    assert jax.config.jax_compilation_cache_dir == conftest._CACHE_DIR
    _a_program_of_this_files_own()(jnp.ones((7, 11)))
    assert held < _entries() and len(_entries()) == len(held) + 1


def test_cold_compile_takes_the_directory_away_and_puts_it_back(after_cold_compile, cold_compile):
    """Inside the fixture there is no directory, and a new program leaves no
    entry in the old one; ``after_cold_compile`` looks at what follows."""
    assert jax.config.jax_compilation_cache_dir is None
    _a_program_of_this_files_own()(jnp.ones((7, 11)))
    assert _entries() == after_cold_compile


def test_a_program_over_several_devices_is_never_an_entry(keep_every_program, eight_devices):
    """Loaded back, such a program's collectives meet out of step on this
    installation's CPU runtime and the process aborts (``conftest.py``): it is
    compiled every time. The same sum on one device is an entry."""
    mesh = jax.sharding.Mesh(np.asarray(eight_devices), ("data", ))
    rows = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    ones, spread = jnp.ones((8, 4)), jax.device_put(jnp.ones((8, 4)), rows)
    held = _entries()
    over_eight = jax.jit(lambda a: (a * 1.75).sum())(spread)
    assert _entries() == held
    on_one = jax.jit(lambda a: (a * 1.75).sum())(ones)
    assert len(_entries()) == len(held) + 1 and float(over_eight) == float(on_one) == 56.0


def test_the_first_out_files_exist_and_none_is_named_twice():
    assert conftest._FIRST_OUT, "the tuple names the long files"
    assert len(set(conftest._FIRST_OUT)) == len(conftest._FIRST_OUT)
    missing = [name for name in conftest._FIRST_OUT if not os.path.isfile(os.path.join(TESTS, name))]
    assert not missing, missing


def test_the_first_out_files_are_collected_first_in_the_tuples_order():
    """What every xdist worker does to its collection, on a collection of
    paths: the named files first, as the tuple orders them, the rest as
    collected; and the scheduler's own reordering by number of tests is off."""
    class Item:
        def __init__(self, name, case):
            self.path, self.nodeid = os.path.join(TESTS, name), f"tests/{name}::{case}"

    class Config:
        option = type("Option", (), {"loadscopereorder": True})()

        def getoption(self, name):
            return False

        def addinivalue_line(self, *a):
            pass

    last, first = conftest._FIRST_OUT[-1], conftest._FIRST_OUT[0]
    items = [Item("test_aaa.py", "x"), Item(last, "a"), Item("test_zzz.py", "y"), Item(first, "a"),
             Item("test_aaa.py", "z"), Item(last, "b"), Item(first, "b")]
    conftest.pytest_collection_modifyitems(Config(), items)
    assert [i.nodeid.split("/", 1)[1] for i in items] == [
        f"{first}::a", f"{first}::b", f"{last}::a", f"{last}::b", "test_aaa.py::x", "test_zzz.py::y", "test_aaa.py::z"]
    config = Config()
    conftest.pytest_configure(config)
    assert config.option.loadscopereorder is False


def test_a_test_past_its_limit_fails_by_name_with_the_stacks(tmp_path):
    """A two-line test that sleeps, under this harness's fixture with the
    limit patched down to a second in the sub-process's own conftest."""
    (tmp_path / "conftest.py").write_text(textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("harness", {os.path.join(TESTS, "conftest.py")!r})
        harness = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(harness)
        harness._TEST_LIMIT_S = 1
        _test_limit = harness._test_limit
    """))
    (tmp_path / "test_sleeps.py").write_text("import time\ndef test_it_sleeps_past_the_limit(): time.sleep(30)\n")
    proc = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", "-p", "no:randomly",
                           "test_sleeps.py"], cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "test_sleeps.py::test_it_sleeps_past_the_limit ran past its limit of 1 s" in proc.stdout
    assert "1 failed" in proc.stdout
    assert 'test_sleeps.py", line 2 in test_it_sleeps_past_the_limit' in proc.stdout, "every thread's stack"
