"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's multi-node-on-one-machine test strategy
(/root/reference/python/ray/tests/conftest.py ray_start_cluster +
cluster_utils.Cluster): distributed behavior is exercised locally, here with
8 virtual XLA host devices standing in for a TPU slice.
"""

import os

# JAX_PLATFORMS is the one switch between CPU and chip, and every process
# the suite starts inherits it: tests are hermetic on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("RAY_TPU_OBJECT_STORE_MEMORY_MB", "256")
os.environ.setdefault("RAY_TPU_WORKER_POOL_INITIAL_SIZE", "1")
# Per-node dashboard agents default ON in production; in the suite they
# would add a subprocess per nodelet across hundreds of cluster boots.
# The dedicated agent test re-enables them via GlobalConfig.update.
os.environ.setdefault("RAY_TPU_DASHBOARD_AGENT", "0")
# ONE compile cache for the run: the xdist workers, and the processes the
# tests start, compile the same tiny programs again and again (a module's
# tests are dealt to several workers, `_compiled_programs_let_go` drops a
# worker's own), and a tenth of the suite's summed time was those repeats.
# Every program counts (no minimum compile time or size: the suite's are
# small), the directory is emptied when a run starts and when it ends
# (`_empty_own_compile_cache`), and a directory the caller set is left as it
# is.
_OWN_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache", "cpu-tests")
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _OWN_COMPILE_CACHE
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    # XLA's loader says of EVERY program read back that the compiling
    # machine's feature list names two tuning switches the host's does not
    # (this same machine's): an error-level line a load, in the middle of a
    # line of the progress dots the tier-1 command counts
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402

#: the files whose tests take longest in all (seconds a file summed, a whole
#: run of PR 44 on the CPU: 788 down to 25), longest first.  They go to the
#: head of the run in this order (`pytest_collection_modifyitems`); every
#: other file, none over 25 s, follows where it was.  A new file of
#: compile-heavy model tests belongs here, by its weight (PR 46's two by
#: their times alone: `test_sparse_index.py` 142 s,
#: `test_perfbench_family_glm_moe_dsa.py` 117 s; PR 55's two, entered by
#: PR 57 beside the neighbours it timed in one process: `test_ssd.py` 104 s
#: then `test_perfbench_family_dots3_note.py` 99 s, `test_window_latent.py`
#: 67 s then `test_short_conv_state.py` 56 s; PR 63's two by their times
#: alone in one process: `test_perfbench_family_longcat_flash.py` 52 s,
#: `test_shortcut_moe.py` 33 s).  `test_scale.py`
#: (230-290 s) stands later than its weight: its floor on tasks a second
#: (400; 480 read beside the runtime's own tests, 365 and 378 beside five
#: workers compiling) wants the light end of the run, where it still ends
#: with the others.
_LONGEST_FIRST = (
    "test_perfbench_family_afmoe.py", "test_perfbench_family_lfm2_moe.py",
    "test_chip_compile.py", "test_perfbench_family_glm4_moe_lite.py",
    "test_ops_models.py", "test_dqn_sac.py", "test_examples.py",
    "test_perfbench_rehearsal.py", "test_sparse_index.py",
    "test_perfbench_family_glm_moe_dsa.py",
    "test_perfbench_family_kimi_linear.py", "test_delta_rule.py",
    "test_perfbench_family_falcon_h1.py", "test_ssd.py",
    "test_perfbench_family_dots3_note.py",
    "test_perfbench_family_phi4flash.py",
    "test_perfbench_family_mimo_v2_flash.py", "test_mixed_kv_heads.py",
    "test_serve_decode_engine.py", "test_prefill_padded_tail.py",
    "test_window_ring.py", "test_window_latent.py",
    "test_short_conv_state.py", "test_perfbench_family_longcat_flash.py",
    "test_shared_cache.py", "test_gbdt.py",
    "test_rl.py", "test_latent_moe.py", "test_dt.py",
    "test_multi_agent.py", "test_grouped_matmul.py",
    "test_perfbench_family_evabyte.py", "test_shortcut_moe.py",
    "test_generate.py",
    "test_dreamer.py", "test_rl_breadth.py", "test_prefill_lanes.py",
    "test_external_env.py", "test_perfbench_engine_ahead.py",
    "test_pipeline_moe.py", "test_serve_failover.py",
    "test_program_parts.py", "test_perfbench_reference.py",
    "test_apex.py", "test_hf_trainer.py", "test_diff_attention.py",
    "test_eva_attention.py", "test_selective_scan.py",
    "test_cache_attention.py", "test_cache_in_place.py",
    "test_pixel_pong.py", "test_scale.py",
    "test_offline_rl.py", "test_rl_plumbing.py", "test_tune.py",
    "test_alpha_zero.py", "test_maml.py",
    "test_train.py", "test_refcounting.py", "test_slateq.py",
    "test_serve_autoscale.py", "test_serve.py",
    "test_perfbench_chunks_per_program.py", "test_partition.py",
    "test_serve_gang.py", "test_ring_attention.py", "test_chip_smoke.py",
    "test_cache_column_write.py", "test_serve_breakdown.py",
    "test_framework_lint.py", "test_impala.py")


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run test on a fresh event loop")
    config.addinivalue_line(
        "markers",
        "slow: heavy multi-process scenario excluded from tier-1 "
        "(-m 'not slow'); `make chaos` runs them")
    _empty_own_compile_cache(config)


def pytest_unconfigure(config):
    _empty_own_compile_cache(config)


def _empty_own_compile_cache(config):
    """By the run's controller (or a run without xdist), at both ends: a run
    starts cold, and leaves no 90 MB in the checkout."""
    ours = os.environ.get("JAX_COMPILATION_CACHE_DIR") == _OWN_COMPILE_CACHE
    if ours and not hasattr(config, "workerinput"):
        import shutil
        shutil.rmtree(_OWN_COMPILE_CACHE, ignore_errors=True)


def pytest_collection_modifyitems(items):
    """`_LONGEST_FIRST`'s files to the head of the run, each file's tests
    together and in their order (the same on every worker: xdist compares
    the collections)."""
    rank = {name: i for i, name in enumerate(_LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(
        os.path.basename(str(item.path)), len(rank)))


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """Under ``--dist load`` (what ``-n`` alone means, and what the tier-1
    command says) a worker is handed whole FILES, in the run's order, a new
    one when it has two tests left.  xdist's own ``load`` hands out runs of
    consecutive tests, a twelfth of what is left at a time (71 tests at the
    start of this suite, then ~100): the first two such runs were the four
    slowest `tests/benchmark` files, two workers of six ran past the tier-1
    command's limit on them alone while four went idle, and a file cut
    across workers pays its module fixtures and every compile of its
    programs once a worker.  Longest file first over six workers is list
    scheduling: the run ends within a light file of the summed time / 6."""
    if config.getoption("dist") != "load":
        return None
    from xdist.scheduler import LoadFileScheduling
    config.option.loadscopereorder = False      # the run's order, not sizes
    return LoadFileScheduling(config, log)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Minimal stand-in for pytest-asyncio (not in the image)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {k: pyfuncitem.funcargs[k]
                  for k in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture(autouse=True, scope="module")
def _no_runtime_left_behind():
    """A module that initialised the runtime implicitly (any API call does)
    and never shut it down would make every later `ray_tpu.init()` on the
    same xdist worker fail with "called twice"."""
    yield
    import ray_tpu
    if ray_tpu.is_initialized():
        from ray_tpu import serve
        serve.shutdown()
        ray_tpu.shutdown()


#: a process may hold `vm.max_map_count` (65530) memory mappings, and every
#: program XLA compiles for the CPU keeps a few: an xdist worker that is
#: handed three model families' files in a row passes it, and the next
#: compile dies (a segmentation fault, an abort or a MemoryError)
_MAPS_HIGH = 30000


@pytest.fixture(autouse=True, scope="module")
def _compiled_programs_let_go():
    """After a module, where this process's mappings have grown past
    `_MAPS_HIGH`, drop JAX's caches of compiled programs (the next module
    compiles what it runs anyway)."""
    yield
    import gc
    import sys
    if "jax" not in sys.modules:
        return
    try:
        with open("/proc/self/maps") as f:
            maps = sum(1 for _ in f)
    except OSError:
        return
    if maps > _MAPS_HIGH:
        sys.modules["jax"].clear_caches()
        gc.collect()


#: the files whose time on the CPU is COMPILING the programs of a serving
#: engine over a tiny model, each run a few times: this layer's serving
#: tests, and the three benchmark family files built the same way.  Not
#: here: anything that trains or runs for its time (the RL files: DQN's one
#: test takes 2.5 times the CPU unoptimized), the files whose time is eager
#: tracing and lowering (`test_ops_models.py`, the afmoe, lfm2 and glm
#: family files: nothing gained), `test_chip_compile.py` (the option would
#: reach the chip's compiler, whose output it reads).
_COMPILE_BOUND = frozenset((
    "test_window_ring.py", "test_short_conv_state.py",
    "test_prefill_padded_tail.py", "test_mixed_kv_heads.py",
    "test_latent_moe.py", "test_prefill_lanes.py",
    "test_serve_decode_engine.py", "test_generate.py",
    "test_perfbench_family_mimo_v2_flash.py",
    "test_perfbench_family_evabyte.py", "test_perfbench_reference.py"))


@pytest.fixture(autouse=True, scope="module")
def _compile_bound_files_skip_the_code_generators_optimizer(request):
    """For a file of `_COMPILE_BOUND`, JAX's ``jax_disable_most_optimizations``
    ("useful if the cost of optimization is greater than that of running a
    less-optimized program"): the same HLO through LLVM at its level 0, a
    quarter to a half off those files' time (`test_window_ring.py` alone:
    82 -> 61 s), and every test of them asserts what it asserted, to the
    tolerance it had.  Set in the environment too, for the processes such a
    file starts.  The option is no part of `jax.jit`'s key, so the worker's
    compiled programs are dropped where it changes: a later file never runs
    a program this one compiled (`test_perfbench_family_gpt2.py` holds a
    reference to a golden file bit for bit)."""
    if os.path.basename(str(request.path)) not in _COMPILE_BOUND:
        yield
        return
    import jax
    name = "jax_disable_most_optimizations"
    was, was_env = jax.config.read(name), os.environ.get(name.upper())
    jax.clear_caches()
    jax.config.update(name, True)
    os.environ[name.upper()] = "1"
    yield
    jax.clear_caches()
    jax.config.update(name, was)
    if was_env is None:
        del os.environ[name.upper()]
    else:
        os.environ[name.upper()] = was_env


@pytest.fixture
def local_cluster():
    """A started single-node runtime, shut down afterwards."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()
