#!/usr/bin/env python3
"""Chip smoke test: the DSEKL main path, end to end, on a TPU.

Runs in ONE process (it starts no child), through the entry points a user
calls, in this order:

  1. device   - a TPU must be attached and ``impl="auto"`` must resolve to
                the Pallas kernels; a ``REPRO_IMPL`` other than ``pallas``
                is refused.
  2. one step - the fused Pallas train pass (rbf, hinge) on one
                1024 x 1024 block, and the laplacian dual pass, each against
                a plain float32 ``jax.numpy`` reference computed at
                ``highest`` matmul precision.
  3. train    - ``repro.core.fit`` on the paper's covertype shape
                (581,012 x 54 float32, generated from ``--seed``; nothing is
                downloaded) through the in-memory serial plan: rbf, hinge,
                n_grad = n_expand = 1024, the default fused train pass.
  4. serve    - ``DSEKLPredictionEngine`` from the trained alpha (truncate
                -> pad), micro-batched requests through ``submit`` /
                ``flush_async``, checked against the float32 reference
                decision function.

``--chips 4`` runs only the multi-chip checks instead: a ``data_par=4``
mesh fit against a single-device replay of the same steps, and the
support-set-sharded engine against the single-device engine.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failed phase raises, exits nonzero and prints no such line.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the four-chip checks
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# Covertype (paper section 4.2): 581,012 rows x 54 features.
N_ROWS, DIM = 581_012, 54
N_VAL = 8192
BLOCK = 1024                  # n_grad = n_expand
EPOCHS = 2
GAMMA_TRAIN = 1.0             # the repo's covertype setting
# Tolerances, elementwise, relative to the same sum taken over |terms|
# (|K| @ |a|): a true float32 kernel stays ~1e-6 below it, a bf16 cross
# term would not.
STEP_RTOL = 1e-4
SERVE_RTOL = 5e-4
# The validation error must beat predicting the majority class by this.
VAL_MARGIN = 0.05
# Four chips: a hinge step is discontinuous at the margin, so the mesh's
# psum order may flip a rare row; a wrong shard layout moves alpha by O(1).
MESH_ALPHA_RTOL = 1e-2
MESH_VAL_ATOL = 0.01


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


if not os.path.isdir(os.path.join(SRC, "repro")):
    fail(f"no {SRC}/repro next to this script; run it from a checkout of "
         "the repository")
if os.environ.get("REPRO_IMPL", "") not in ("", "pallas"):
    fail(f"REPRO_IMPL={os.environ['REPRO_IMPL']!r} would move the kernels "
         "off the chip; unset it or set it to 'pallas'")
sys.path.insert(0, SRC)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from repro.launch.compile_cache import setup_compile_cache  # noqa: E402


# ---------------------------------------------------------------------------
# Plain float32 references (independent of the code under test).
# ---------------------------------------------------------------------------

def ref_rbf(xq, xs, gamma):
    with jax.default_matmul_precision("highest"):
        sq = (jnp.sum(xq * xq, 1)[:, None] + jnp.sum(xs * xs, 1)[None, :]
              - 2.0 * xq @ xs.T)
    return jnp.exp(-gamma * jnp.maximum(sq, 0.0))


def ref_laplacian(xq, xs, gamma):
    return jnp.exp(-gamma * jnp.sum(jnp.abs(xq[:, None, :] - xs[None]), -1))


def hinge_grad(f, y):
    return jnp.where(y * f < 1.0, -y, 0.0)


def mv(k, a):
    with jax.default_matmul_precision("highest"):
        return k @ a, jnp.abs(k) @ jnp.abs(a)


@jax.jit
def ref_decision(xq, xs, a):
    """(K(xq, xs) @ a, K @ |a|) at gamma=GAMMA_TRAIN, 8192 SV rows at a time."""
    pad = (-xs.shape[0]) % 8192
    xs = jnp.pad(xs, ((0, pad), (0, 0))).reshape(-1, 8192, xs.shape[1])
    a = jnp.pad(a, (0, pad)).reshape(-1, 8192)

    def body(acc, tile):
        f, fa = mv(ref_rbf(xq, tile[0], GAMMA_TRAIN), tile[1])
        return (acc[0] + f, acc[1] + fa), None

    zero = jnp.zeros((xq.shape[0],), jnp.float32)
    return jax.lax.scan(body, (zero, zero), (xs, a))[0]


def within(got, want, scale, rtol):
    """Worst |got - want| / (rtol * scale + 1e-6); <= 1 passes."""
    err = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    return float(np.max(err / (rtol * np.asarray(scale, np.float64) + 1e-6)))


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------

def phase_device(n_chips: int):
    from repro.kernels.dsekl import ops as kops

    devs = jax.devices()
    d0 = devs[0]
    log(f"jax {jax.__version__}; platform={d0.platform} "
        f"kind={d0.device_kind!r} count={len(devs)}")
    check(d0.platform == "tpu",
          f"JAX found no TPU (platform {d0.platform!r}); this script "
          "measures nothing on another backend")
    check(len(devs) >= n_chips, f"--chips {n_chips} but JAX sees "
          f"{len(devs)} device(s)")
    impl = kops.resolve_impl("auto", "rbf")
    check(impl == "pallas", f"impl='auto' resolved to {impl!r}, not pallas")
    return d0


def make_data(seed: int):
    from repro.data.synthetic import make_covertype_like

    x, y = make_covertype_like(jax.random.PRNGKey(seed), n=N_ROWS, d=DIM)
    x, y = jax.block_until_ready((x, y))
    return x[:-N_VAL], y[:-N_VAL], x[-N_VAL:], y[-N_VAL:]


def phase_step(x, y, seed: int) -> None:
    from repro.kernels.dsekl import ops as kops

    ka, kv = jax.random.split(jax.random.PRNGKey(seed + 1))
    xi, yi = x[:BLOCK], y[:BLOCK]
    xj = x[BLOCK:2 * BLOCK]
    a = jax.random.normal(ka, (BLOCK,), jnp.float32) / np.sqrt(BLOCK)

    # Fused train pass: f = K a, v = hinge'(f, y), g = K^T v in one kernel.
    gamma = 0.05            # K entries of order exp(-1.5) on this data
    params = (("gamma", gamma),)
    args = (xi, xj, a, yi)
    kw = dict(kernel_name="rbf", kernel_params=params, loss="hinge",
              impl="pallas")
    hlo = kops.kernel_dual_pass.lower(*args, **kw).compile().as_text()
    check("tpu_custom_call" in hlo, "train pass compiled without a kernel")
    f, g = jax.block_until_ready(kops.kernel_dual_pass(*args, **kw))
    k = ref_rbf(xi, xj, gamma)
    f_ref, f_abs = mv(k, a)
    v = hinge_grad(f, yi)               # the kernel's own margin decisions
    flips = int(jnp.sum(v != hinge_grad(f_ref, yi)))
    g_ref, g_abs = mv(k.T, v)
    ef, eg = within(f, f_ref, f_abs, STEP_RTOL), within(g, g_ref, g_abs,
                                                        STEP_RTOL)
    log(f"step: rbf train pass {BLOCK}x{BLOCK}x{DIM} vs float32 reference: "
        f"f err {ef:.3g}, g err {eg:.3g} of tolerance (rtol {STEP_RTOL}); "
        f"{flips} hinge margin flips")
    check(ef <= 1.0 and eg <= 1.0, "train pass disagrees with the reference")
    check(flips <= BLOCK // 200, f"{flips} hinge margin flips")

    # Laplacian dual pass (v given).
    gamma = 0.05
    v = jax.random.normal(kv, (BLOCK,), jnp.float32)
    f, g = jax.block_until_ready(kops.kernel_dual_pass(
        xi, xj, a, v, kernel_name="laplacian", kernel_params=(("gamma",
                                                               gamma),),
        impl="pallas"))
    k = ref_laplacian(xi, xj, gamma)
    f_ref, f_abs = mv(k, a)
    g_ref, g_abs = mv(k.T, v)
    ef, eg = within(f, f_ref, f_abs, STEP_RTOL), within(g, g_ref, g_abs,
                                                        STEP_RTOL)
    log(f"step: laplacian dual pass vs float32 reference: f err {ef:.3g}, "
        f"g err {eg:.3g} of tolerance")
    check(ef <= 1.0 and eg <= 1.0, "laplacian pass disagrees")


def train_config(n: int):
    from repro.core import DSEKLConfig

    return DSEKLConfig(n_grad=BLOCK, n_expand=BLOCK, kernel="rbf",
                       kernel_params=(("gamma", GAMMA_TRAIN),),
                       loss="hinge", lam=1.0 / n, lr0=1.0,
                       schedule="inv_epoch", impl="auto")


def phase_train(x, y, xv, yv, seed: int):
    from repro.core import dsekl, fit, trainer

    n = int(x.shape[0])
    cfg = train_config(n)
    key = jax.random.PRNGKey(seed + 2)
    t0 = time.perf_counter()
    compiled = trainer._epoch_serial.lower(
        cfg, dsekl.init_state(n), x, y, key).compile()
    t_compile = time.perf_counter() - t0
    n_kernels = compiled.as_text().count("tpu_custom_call")
    check(n_kernels > 0, "the compiled epoch holds no Pallas kernel")
    steps = n // BLOCK
    log(f"train: epoch program compiled in {t_compile:.3f}s "
        f"({n_kernels} tpu_custom_call op(s); {steps} steps/epoch)")

    t0 = time.perf_counter()
    res = fit(cfg, x, y, key, execution="serial", n_epochs=EPOCHS, tol=0.0,
              x_val=xv, y_val=yv)
    t_fit = time.perf_counter() - t0
    errs = [h["val_error"] for h in res.history]
    majority = float(min(jnp.mean(yv > 0), jnp.mean(yv < 0)))
    bound = majority - VAL_MARGIN
    step_s = res.history[-1]["seconds"] / steps
    log(f"train: fit {EPOCHS} epochs x {steps} steps in {t_fit:.3f}s; "
        f"epoch seconds {[round(h['seconds'], 4) for h in res.history]}; "
        f"steady step {step_s * 1e6:.1f} us")
    log(f"train: val error {errs} (majority-class error {majority:.4f}, "
        f"bound {bound:.4f}); |dalpha| "
        f"{[round(h['delta_alpha'], 3) for h in res.history]}")
    check(all(np.isfinite(errs)), "validation error is not finite")
    check(errs[-1] < bound, f"val error {errs[-1]:.4f} >= bound {bound:.4f}")
    check(bool(jnp.all(jnp.isfinite(res.state.alpha))), "alpha not finite")
    return cfg, res.state.alpha


def phase_serve(cfg, alpha, x, xv) -> None:
    from repro.serving import DSEKLPredictionEngine

    t0 = time.perf_counter()
    eng = DSEKLPredictionEngine(cfg, alpha, x)
    st = eng.stats()
    log(f"serve: engine built in {time.perf_counter() - t0:.3f}s; support "
        f"set {st['n_sv']} of {st['n_train']} rows (padded "
        f"{st['n_sv_padded']}), query_block {st['query_block']}")
    sizes = [1, 7, 64, 100, 256, 333, 512, 1000, 1024, 797]
    starts = np.cumsum([0] + sizes)
    reqs = [xv[s:s + m] for s, m in zip(starts, sizes)]
    lat = []
    for sweep in range(2):          # sweep 0 compiles, sweep 1 is warm
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        outs = eng.flush_async()
        lat.append(time.perf_counter() - t0)
        check(len(outs) == len(reqs), "flush returned the wrong count")
    f = jnp.concatenate(outs)
    q = jnp.concatenate(reqs)
    a_sv, x_sv = np.asarray(alpha), np.asarray(x)
    keep = np.abs(a_sv) > 1e-8
    f_ref, f_abs = ref_decision(q, jnp.asarray(x_sv[keep]),
                                jnp.asarray(a_sv[keep]))
    err = within(f, f_ref, f_abs, SERVE_RTOL)
    log(f"serve: {len(reqs)} requests / {int(q.shape[0])} queries per sweep,"
        f" {eng.serve_calls} serve calls in total; sweep seconds "
        f"{[round(t, 4) for t in lat]} (first compiles)")
    log(f"serve: vs float32 reference decision function: err {err:.3g} of "
        f"tolerance (rtol {SERVE_RTOL}); decisions differ on "
        f"{int(jnp.sum((f >= 0) != (f_ref >= 0)))} queries")
    check(err <= 1.0, "served predictions disagree with the reference")


def phase_mesh(x, y, xv, yv, seed: int):
    from repro.core import distributed as dist
    from repro.core import dsekl, fit, trainer
    from repro.data import HostSource
    from repro.launch.mesh import make_local_mesh

    n = int(x.shape[0])
    check(n % 4 == 0, f"{n} rows do not split over 4 data shards")
    cfg = train_config(n)
    key = jax.random.PRNGKey(seed + 3)
    mesh = make_local_mesh(4, 1)
    src = HostSource(np.asarray(x), np.asarray(y))
    t0 = time.perf_counter()
    res = fit(cfg, src, None, key, execution="mesh", mesh=mesh, n_epochs=1,
              tol=0.0, x_val=xv, y_val=yv)
    t_mesh = time.perf_counter() - t0
    alpha_m = res.state.alpha
    check(len(alpha_m.sharding.device_set) == 4,
          f"mesh alpha lives on {len(alpha_m.sharding.device_set)} device(s)")

    # The same steps on one device: the mesh plan's key chain through
    # dist.simulate_step (per-shard sampling, sequential shard sums).
    steps = n // (cfg.n_grad * 4)
    sim = jax.jit(dist.simulate_step, static_argnums=(0, 1, 2))
    st = dsekl.init_state(n)
    alpha, accum, t = st.alpha, st.accum, st.step
    _, sub = jax.random.split(key)
    t0 = time.perf_counter()
    for k in jax.random.split(sub, steps):
        alpha, accum, t = sim(cfg, 4, 1, x, y, alpha, accum, t, k, epoch=1)
    alpha = jax.block_until_ready(alpha)
    t_sim = time.perf_counter() - t0
    err_m = res.history[-1]["val_error"]
    err_s = float(trainer._error(cfg, alpha, x, xv, yv))
    am, a1 = np.asarray(alpha_m, np.float64), np.asarray(alpha, np.float64)
    rel = float(np.linalg.norm(am - a1) / max(np.linalg.norm(a1), 1e-30))
    log(f"mesh: data_par=4 fit, {steps} steps in {t_mesh:.3f}s; "
        f"single-device replay in {t_sim:.3f}s")
    log(f"mesh: alpha rel L2 diff {rel:.3g} (tol {MESH_ALPHA_RTOL}); "
        f"val error mesh {err_m:.4f} vs replay {err_s:.4f} "
        f"(tol {MESH_VAL_ATOL}); steps {int(res.state.step)} vs {int(t)}")
    check(int(res.state.step) == int(t) == steps, "step counts differ")
    check(rel <= MESH_ALPHA_RTOL, "mesh fit disagrees with the replay")
    check(abs(err_m - err_s) <= MESH_VAL_ATOL, "val errors disagree")
    return cfg, alpha_m


def phase_mesh_serve(cfg, alpha, x, xv) -> None:
    from repro.launch.mesh import make_local_mesh
    from repro.serving import DSEKLPredictionEngine

    mesh = make_local_mesh(4, 1)
    alpha = jnp.asarray(np.asarray(alpha))
    eng4 = DSEKLPredictionEngine(cfg, alpha, x, mesh=mesh)
    eng1 = DSEKLPredictionEngine(cfg, alpha, x)
    eng_abs = DSEKLPredictionEngine(cfg, jnp.abs(alpha), x)
    shards = eng4._x_sv.addressable_shards
    rows = {s.device.id: s.data.shape[0] for s in shards}
    check(len(rows) == 4 and set(rows.values()) == {eng4.n_sv_padded // 4},
          f"support set not split over 4 devices: {rows}")
    sizes = [64, 1000, 512, 333]
    starts = np.cumsum([0] + sizes)
    reqs = [xv[s:s + m] for s, m in zip(starts, sizes)]
    outs = {}
    for name, eng in (("sharded", eng4), ("single", eng1), ("abs", eng_abs)):
        for r in reqs:
            eng.submit(r)
        outs[name] = jnp.concatenate(eng.flush_async())
    err = within(outs["sharded"], outs["single"], outs["abs"], SERVE_RTOL)
    log(f"mesh serve: support set {eng4.n_sv} rows over devices {rows}; "
        f"sharded vs single-device engine err {err:.3g} of tolerance "
        f"(rtol {SERVE_RTOL})")
    check(err <= 1.0, "sharded engine disagrees with the single-device one")
    for d in jax.devices()[:4]:
        ms = d.memory_stats() or {}
        log(f"mesh: device {d.id} bytes_in_use {ms.get('bytes_in_use')}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache = setup_compile_cache()
    t_start = time.perf_counter()
    d0 = phase_device(args.chips)
    log(f"compile cache: {cache}")
    x, y, xv, yv = make_data(args.seed)
    log(f"data: covertype-shaped {N_ROWS} x {DIM} f32 (train {x.shape[0]}, "
        f"validation {N_VAL}) from seed {args.seed}")
    if args.chips == 4:
        cfg, alpha = phase_mesh(x, y, xv, yv, args.seed)
        phase_mesh_serve(cfg, alpha, x, xv)
    else:
        phase_step(x, y, args.seed)
        cfg, alpha = phase_train(x, y, xv, yv, args.seed)
        phase_serve(cfg, alpha, x, xv)
    log(f"all phases passed in {time.perf_counter() - t_start:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
