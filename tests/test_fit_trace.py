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
