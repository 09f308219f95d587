"""``fit`` writes its host spans into a profiler trace: ``dsekl.fit``
around the call, its set-up, and one ``dsekl.epoch`` step span per epoch
holding a span per phase (docs/OPERATIONS.md, "Tracing a fit")."""
import glob

import jax
import jax.numpy as jnp
import pytest

from repro.core import DSEKLConfig, fit

PHASES = ("dsekl.epoch.plan", "dsekl.epoch.dispatch", "dsekl.epoch.wait",
          "dsekl.epoch.host_delta", "dsekl.epoch.eval", "dsekl.epoch.hooks",
          "dsekl.epoch.snapshot")
EPOCHS = 2


@pytest.fixture(scope="module")
def host_spans(tmp_path_factory):
    """(name, start_ns, end_ns, stats) of every ``dsekl.`` event on the
    host plane of a traced two-epoch serial fit with validation data."""
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 5))
    y = jnp.where(x[:, 0] * x[:, 1] > 0, 1.0, -1.0)
    cfg = DSEKLConfig(n_grad=32, n_expand=32, impl="ref")
    kw = dict(execution="serial", n_epochs=EPOCHS, tol=0.0, x_val=x[:64],
              y_val=y[:64])
    fit(cfg, x, y, jax.random.PRNGKey(1), **kw)          # compile first
    logdir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(logdir), profiler_options=opts):
        res = fit(cfg, x, y, jax.random.PRNGKey(1),
                  checkpoint_dir=str(logdir / "ckpt"), **kw)
    assert res.epochs_run == EPOCHS
    (path,) = glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith("dsekl.")]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_every_span_is_written_and_nested_in_the_fit(host_spans):
    names = [s[0] for s in host_spans]
    assert names.count("dsekl.fit") == 1
    fit_span = next(s for s in host_spans if s[0] == "dsekl.fit")
    assert all(_inside(s, fit_span) for s in host_spans)
    epochs = sorted((s for s in host_spans if s[0] == "dsekl.epoch"),
                    key=lambda s: s[1])
    assert [s[3]["step_num"] for s in epochs] == list(range(1, EPOCHS + 1))
    setup = [s for s in host_spans if s[0] == "dsekl.fit.setup"]
    assert setup and all(s[2] <= epochs[0][1] for s in setup)
    for phase in PHASES:
        spans = [s for s in host_spans if s[0] == phase]
        assert len(spans) == EPOCHS, phase
        # One in each epoch, in the order fit_loop runs them.
        assert all(_inside(s, e) for s, e in
                   zip(sorted(spans, key=lambda s: s[1]), epochs)), phase
    for e in epochs:
        order = sorted((s for s in host_spans
                        if s[0] in PHASES and _inside(s, e)),
                       key=lambda s: s[1])
        assert tuple(s[0] for s in order) == PHASES


MESH_EPOCHS, MESH_STEPS = 2, 16


@pytest.fixture(scope="module")
def mesh_lines(tmp_path_factory):
    """Per host-plane line, the ``dsekl.`` events (name, start_ns, end_ns) of
    a traced two-epoch mesh fit over a host source (a 1 x 1 mesh: the
    spans do not depend on the mesh's size), and the fit's result."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.data import HostSource

    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (512, 5)))
    y = np.where(x[:, 0] * x[:, 1] > 0, 1.0, -1.0).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    cfg = DSEKLConfig(n_grad=32, n_expand=32, impl="ref",
                      schedule="inv_epoch")
    kw = dict(execution="mesh", mesh=mesh, n_epochs=MESH_EPOCHS, tol=0.0)
    src = HostSource(x, y)
    fit(cfg, src, None, jax.random.PRNGKey(1), **kw)     # compile first
    logdir = tmp_path_factory.mktemp("mesh_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(logdir), profiler_options=opts):
        res = fit(cfg, src, None, jax.random.PRNGKey(1), **kw)
    (path,) = glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events if e.name.startswith("dsekl.")]
             for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines]
    return [ln for ln in lines if ln], res


def test_mesh_step_spans_sit_in_each_epochs_dispatch(mesh_lines):
    lines, _ = mesh_lines
    (own,) = [ln for ln in lines if any(e[0] == "dsekl.fit" for e in ln)]
    dispatch = sorted((e for e in own if e[0] == "dsekl.epoch.dispatch"),
                      key=lambda e: e[1])
    assert len(dispatch) == MESH_EPOCHS
    for d in dispatch:
        inner = sorted((e for e in own if e[0].startswith("dsekl.mesh.")
                        and _inside(e, d)), key=lambda e: e[1])
        assert [e[0] for e in inner] == \
            ["dsekl.mesh.wait", "dsekl.mesh.step"] * MESH_STEPS
    assert sum(e[0].startswith("dsekl.mesh.") for e in own) == \
        2 * MESH_STEPS * MESH_EPOCHS


def test_mesh_prefetcher_spans_run_on_its_own_thread(mesh_lines):
    lines, res = mesh_lines
    (worker,) = [ln for ln in lines if any(e[0] == "dsekl.mesh.gather"
                                           for e in ln)]
    assert not any(e[0] == "dsekl.fit" for e in worker)
    names = [e[0] for e in sorted(worker, key=lambda e: e[1])]
    assert set(names) == {"dsekl.mesh.gather", "dsekl.mesh.h2d"}
    # Each step's gather, then its copy; the worker may run ahead of the
    # fit into the epoch planned next.
    assert names[::2] == ["dsekl.mesh.gather"] * (len(names) // 2)
    assert names[1::2] == ["dsekl.mesh.h2d"] * (len(names) // 2)
    assert len(names) // 2 >= res.loader["steps"] == MESH_STEPS * MESH_EPOCHS


def test_mesh_loader_stats_count_the_steps_not_ready(mesh_lines):
    _, res = mesh_lines
    ld = res.loader
    assert set(ld) == {"steps", "gather_s", "wait_s", "not_ready"}
    assert 0 <= ld["not_ready"] <= ld["steps"] and ld["wait_s"] >= 0.0
