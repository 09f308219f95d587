"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production meshes and extract roofline inputs from the compiled artifact.

MUST set XLA_FLAGS before any jax import (jax locks the device count at
first init) — hence the first two lines.  Run one cell per process:

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-20b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]

or the full sweep (spawns one subprocess per cell, resumable):

    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", ""))

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, SHAPES, applicable, get_config  # noqa: E402
from repro.configs.shapes import rules_kind  # noqa: E402
from repro.distributed.sharding import MeshCtx, make_rules  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import blocks  # noqa: E402
from repro.models.model import LanguageModel  # noqa: E402
from repro.optim import make_optimizer, make_schedule  # noqa: E402
from repro.train.step import make_train_step  # noqa: E402

DEFAULT_OUT = "experiments/dryrun"

# Archs whose decode KV cache cannot shard kv_heads 16-way: shard the cache
# sequence over the model axis instead (distributed flash-decode; the
# softmax reduction over the sharded axis becomes an all-reduce).
_KV_SEQ_OVER_MODEL = {
    "granite-20b", "starcoder2-15b", "internlm2-20b", "whisper-tiny",
    "kimi-k2-1t-a32b", "deepseek-v3-671b", "llama-3.2-vision-11b",
    "jamba-v0.1-52b",
}

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(text: str) -> int:
    """Sum bytes over every `dtype[d0,d1,...]` group in ``text``."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo: str) -> dict:
    """Per-collective result bytes from (post-SPMD, per-device) HLO text."""
    out = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+(" + "|".join(_COLLECTIVES)
                     + r")(?:-start|-done)?\(", line)
        if not m:
            continue
        shape_part, op = m.group(1), m.group(2)
        if "-done(" in line:       # avoid double counting async pairs
            continue
        if "-start(" in line:
            # async start result is a tuple (operand, result, ...):
            # count the RESULT shape only (second group).
            groups = re.findall(r"\w+\[[\d,]*\]", shape_part)
            if len(groups) >= 2:
                shape_part = groups[1]
        out[op]["count"] += 1
        out[op]["bytes"] += _shape_bytes(shape_part)
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def _ns(mesh, tree_pspec):
    return jax.tree.map(lambda ps: NamedSharding(mesh, ps), tree_pspec,
                        is_leaf=lambda x: isinstance(x, P))


def _opt_pspecs(opt_name: str, params_ps):
    out = {"count": P()}
    if opt_name == "adamw":
        out["m"] = params_ps
        out["v"] = params_ps
    elif opt_name == "adagrad":
        out["g2"] = params_ps
    elif opt_name == "momentum":
        out["m"] = params_ps
    return out


# --- §Perf hillclimb variants: named deltas applied on top of a cell ----
# rules: sharding-rule overrides; cfg: ModelConfig overrides; step: kwargs
# for the train-step builder (loss_chunks / remat / microbatches).
VARIANTS = {
    # decode: keep weights TP-sharded only (no ZeRO gather per step)
    "no_zero": {"rules": {"embed": None}},
    # train: no activation rematerialization (compute down, memory up)
    "no_remat": {"step": {"remat": False}},
    # train: 4 microbatches of gradient accumulation
    "micro4": {"step": {"microbatches": 4}},
    # MoE: capacity factor 1.0 (20% less dispatch traffic, more drops)
    "cap1": {"cfg": {"capacity_factor": 1.0}},
    # coarser loss chunking (fewer head matmuls in flight)
    "loss32": {"step": {"loss_chunks": 32}},
    # decode long-context: KV cache sharded over model axis too
    "kvseq_model": {"rules": {"kv_seq": "model"}},
    # serving: weights stored fp8 (dequant-on-read halves weight streaming;
    # per-tensor scales omitted in the dry-run — shape-identical)
    "wf8": {"rules": {"embed": None}, "weights_f8": True},
    # small models: drop tensor parallelism entirely (pure DP + ZeRO);
    # a 0.86B model over 16-way TP pays Megatron all-reduces it can't amortize
    "no_tp": {"rules": {"mlp": None, "ssm_heads": None, "heads": None,
                        "kv_heads": None, "vocab": None, "q_lora": None}},
    # ... and give the freed model axis to DATA parallelism (256-way DP,
    # ZeRO-sharded over both axes) so no device duplicates work
    "dp256": {"rules": {"mlp": None, "ssm_heads": None, "heads": None,
                        "kv_heads": None, "vocab": None, "q_lora": None,
                        "batch": ("data", "model"),
                        "moe_tokens": ("data", "model"),
                        "embed": ("data", "model")}},
    # dp256 + halved SSD chunk (intra-chunk dual-form work scales ~Q)
    "dp256_c128": {"rules": {"mlp": None, "ssm_heads": None, "heads": None,
                             "kv_heads": None, "vocab": None, "q_lora": None,
                             "batch": ("data", "model"),
                             "moe_tokens": ("data", "model"),
                             "embed": ("data", "model")},
                   "cfg": {"ssm_chunk": 128}},
}


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: dict = None, n_layers: int = None,
               unroll: bool = False, variant: str = None):
    """Returns (fn, abstract_args, in_shardings, out_shardings, meta)."""
    cfg = get_config(arch)
    var = VARIANTS.get(variant or "", {})
    if var.get("cfg"):
        cfg = cfg.replace(**var["cfg"])
    step_kw = dict(var.get("step", {}))
    if n_layers is not None:
        cfg = cfg.replace(n_layers=n_layers)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = rules_kind(shape)
    rules = make_rules(kind, multi_pod)
    if kind in ("decode",) and arch in _KV_SEQ_OVER_MODEL:
        rules["kv_seq"] = "model"
    for k, v in (overrides or {}).items():
        rules[k] = v
    for k, v in var.get("rules", {}).items():
        rules[k] = v
    dp = ("pod", "data") if multi_pod else ("data",)
    ctx = MeshCtx(mesh=mesh, rules=rules, data_axes=dp, model_axis="model",
                  unroll=unroll)
    model = LanguageModel(cfg)

    axis_sizes = ctx.axis_sizes
    weights_f8 = bool(var.get("weights_f8"))
    params_abs = model.abstract(
        jnp.float8_e4m3fn if weights_f8 else None)
    params_ps = model.pspecs(rules, axis_sizes)
    b, s = shape.global_batch, shape.seq_len
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "params": cfg.param_count_estimate(),
            "active_params": cfg.active_param_count_estimate()}

    frontend_abs = None
    frontend_ps = None
    if cfg.n_frontend_tokens:
        frontend_abs = jax.ShapeDtypeStruct(
            (b, cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)
        frontend_ps = ctx.pspec("batch", "frontend_seq", None,
                                shape=frontend_abs.shape)

    if kind == "train":
        opt = make_optimizer("adamw", make_schedule("cosine", 3e-4,
                                                    warmup_steps=100,
                                                    total_steps=10_000),
                             moment_dtype=jnp.bfloat16)
        step = make_train_step(
            model, ctx, opt,
            loss_chunks=step_kw.pop("loss_chunks", 16),
            remat=step_kw.pop("remat", True), **step_kw)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        opt_ps = _opt_pspecs("adamw", params_ps)
        batch_abs = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        batch_ps = {"tokens": ctx.pspec("batch", "seq", shape=(b, s)),
                    "labels": ctx.pspec("batch", "seq", shape=(b, s))}
        if frontend_abs is not None:
            batch_abs["frontend"] = frontend_abs
            batch_ps["frontend"] = frontend_ps
        in_sh = (_ns(mesh, params_ps), _ns(mesh, opt_ps), _ns(mesh, batch_ps))
        out_sh = (_ns(mesh, params_ps), _ns(mesh, opt_ps),
                  {"loss": NamedSharding(mesh, P()),
                   "grad_norm": NamedSharding(mesh, P())})
        meta["tokens"] = b * s
        return step, (params_abs, opt_abs, batch_abs), in_sh, out_sh, meta

    if kind == "prefill":
        def fn(params, tokens, frontend=None):
            return model.prefill(params, ctx, tokens, s, frontend=frontend)
        tokens_abs = jax.ShapeDtypeStruct((b, s), jnp.int32)
        cache_ps = blocks.stack_cache_pspecs(cfg, rules, b, s,
                                             cfg.n_frontend_tokens,
                                             axis_sizes)
        args = [params_abs, tokens_abs]
        in_list = [_ns(mesh, params_ps),
                   NamedSharding(mesh, ctx.pspec("batch", "seq",
                                                 shape=(b, s)))]
        if frontend_abs is not None:
            args.append(frontend_abs)
            in_list.append(NamedSharding(mesh, frontend_ps))
        out_sh = (NamedSharding(mesh, ctx.pspec("batch", "vocab",
                                                shape=(b, cfg.vocab_size))),
                  _ns(mesh, cache_ps))
        meta["tokens"] = b * s
        return fn, tuple(args), tuple(in_list), out_sh, meta

    # decode / long_decode: one new token against a seq_len cache.
    def fn(params, token, cache, pos):
        if weights_f8:
            from repro.nn.module import cast_floating
            params = cast_floating(params, cfg.cdtype)
        return model.decode_step(params, ctx, token, cache, pos)
    cache_abs = jax.eval_shape(lambda: model.init_cache(b, s))
    cache_ps = blocks.stack_cache_pspecs(cfg, rules, b, s,
                                         cfg.n_frontend_tokens,
                                         axis_sizes)
    tok_abs = jax.ShapeDtypeStruct((b,), jnp.int32)
    pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
    in_sh = (_ns(mesh, params_ps),
             NamedSharding(mesh, ctx.pspec("batch", shape=(b,))),
             _ns(mesh, cache_ps), NamedSharding(mesh, P()))
    out_sh = (NamedSharding(mesh, ctx.pspec("batch", "vocab",
                                            shape=(b, cfg.vocab_size))),
              _ns(mesh, cache_ps))
    meta["tokens"] = b
    return fn, (params_abs, tok_abs, cache_abs, pos_abs), in_sh, out_sh, meta


def build_dsekl_cell(shape_name: str, multi_pod: bool):
    """The paper's technique on the production mesh: distributed DSEKL
    (2-D redundant sharding, core/distributed.py) at production scale.

    dsekl_prod: N = 2^27 synthetic points, D = 128, per-device I = J = 8192
    (effective I = 8192 * |data| per step — the covertype experiment scaled
    ~230x).  dsekl_covtype: the paper's own covertype setting (N = 581012,
    D = 54, I = J = 10000 global).
    """
    from repro.core.dsekl import DSEKLConfig
    from repro.core import distributed as dsekl_dist

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_data = 32 if multi_pod else 16
    if shape_name == "dsekl_prod":
        n, d = 1 << 27, 128
        cfg = DSEKLConfig(n_grad=8192, n_expand=8192, schedule="adagrad",
                          lam=1e-6)
    else:  # dsekl_covtype — paper §4.2 (I=J=10000 split over the mesh)
        n, d = 581_012 // (n_data * 16) * (n_data * 16), 54
        per_dev = max(10_000 // n_data, 64)
        cfg = DSEKLConfig(n_grad=per_dev, n_expand=per_dev,
                          schedule="adagrad", lam=1.0 / 581_012)

    # The distributed step shard_maps over ('data','model') only; fold the
    # pod axis into data for the multi-pod mesh.
    data_axes = ("pod", "data") if multi_pod else ("data",)
    step = dsekl_dist.make_distributed_step(
        cfg, mesh, n, data_axis=data_axes if not multi_pod else data_axes,
        model_axis="model")
    xg = jax.ShapeDtypeStruct((n, d), jnp.float32)
    yg = jax.ShapeDtypeStruct((n,), jnp.float32)
    xe = jax.ShapeDtypeStruct((n, d), jnp.float32)
    state = dsekl_dist.ShardedDSEKLState(
        alpha=jax.ShapeDtypeStruct((n,), jnp.float32),
        accum=jax.ShapeDtypeStruct((n,), jnp.float32),
        step=jax.ShapeDtypeStruct((), jnp.int32),
        epoch=jax.ShapeDtypeStruct((), jnp.int32))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    dpspec = P(data_axes)
    in_sh = (NamedSharding(mesh, P(data_axes, None)),
             NamedSharding(mesh, dpspec),
             NamedSharding(mesh, P("model", None)),
             dsekl_dist.ShardedDSEKLState(
                 alpha=NamedSharding(mesh, P("model")),
                 accum=NamedSharding(mesh, P("model")),
                 step=NamedSharding(mesh, P()),
                 epoch=NamedSharding(mesh, P())),
             NamedSharding(mesh, P()))
    out_sh = dsekl_dist.ShardedDSEKLState(
        alpha=NamedSharding(mesh, P("model")),
        accum=NamedSharding(mesh, P("model")),
        step=NamedSharding(mesh, P()),
        epoch=NamedSharding(mesh, P()))
    n_chips = 512 if multi_pod else 256
    meta = {"arch": "dsekl", "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "params": n, "active_params": n,
            "tokens": cfg.n_grad * n_data,
            # Irreducible DSEKL work: every device evaluates its own
            # (I_loc x J_loc) kernel block at ~(2D + 4) flops/entry (one
            # fused distance-matmul + the two kernel mat-vec products).
            "model_flops_explicit": (
                n_chips * cfg.n_grad * cfg.n_expand * (2 * d + 4))}
    return step, (xg, yg, xe, state, key), in_sh, out_sh, meta


def _donate_args(shape_name: str, donate: bool):
    if not donate:
        return ()
    if shape_name == "train_4k":
        return (0, 1)
    if shape_name in ("decode_32k", "long_500k"):
        return (2,)
    return ()


def _compile_one(arch, shape_name, multi_pod, donate, n_layers=None,
                 unroll=False, variant=None):
    if arch == "dsekl":
        fn, args, in_sh, out_sh, meta = build_dsekl_cell(shape_name,
                                                         multi_pod)
    else:
        fn, args, in_sh, out_sh, meta = build_cell(
            arch, shape_name, multi_pod, n_layers=n_layers, unroll=unroll,
            variant=variant)
    t0 = time.perf_counter()
    jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                  donate_argnums=_donate_args(shape_name, donate))
    lowered = jfn.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    rec = {"seconds_lower": t1 - t0, "seconds_compile": t2 - t1}
    try:
        ca = compiled.cost_analysis()
        rec["cost_analysis"] = {
            "flops": float(ca.get("flops", -1.0)),
            "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
            "transcendentals": float(ca.get("transcendentals", -1.0)),
        }
    except Exception as e:  # pragma: no cover
        rec["cost_analysis"] = {"error": str(e)}
    try:
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(ma, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")
            if hasattr(ma, k)}
    except Exception as e:  # pragma: no cover
        rec["memory_analysis"] = {"error": str(e)}
    try:
        hlo = compiled.as_text()
        rec["collectives"] = parse_collectives(hlo)
        rec["hlo_bytes"] = len(hlo)
    except Exception as e:  # pragma: no cover
        rec["collectives"] = {"error": str(e)}
    return rec, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             donate: bool = True, variant: str = None) -> dict:
    """Dry-run one cell.

    1. Lower + compile the PRODUCTION artifact (scan-over-periods).  This
       is the required dry-run pass; memory_analysis comes from it.
    2. Compile two small UNROLLED probes (1 period + remainder, 2 periods
       + remainder).  XLA cost analysis counts a while body once, so true
       totals are linear-extrapolated:  total = probe1 + (n_periods - 1) *
       (probe2 - probe1) — exact because periods are structurally
       identical.  FLOPs/bytes/collective-bytes all use this.
    """
    if arch == "dsekl":
        # No scan inside the DSEKL step: cost_analysis is already exact.
        full_rec, meta = _compile_one(arch, shape_name, multi_pod, donate)
        meta["variant"] = variant
        rec = dict(meta)
        rec.update(full_rec)
        rec["roofline_inputs"] = {
            "flops": full_rec["cost_analysis"].get("flops"),
            "bytes_accessed": full_rec["cost_analysis"].get("bytes_accessed"),
            "collective_bytes": full_rec["collectives"].get("total_bytes"),
            "collectives_by_op": {
                op: full_rec["collectives"][op]["bytes"]
                for op in _COLLECTIVES if op in full_rec["collectives"]},
            "method": "direct (no scan in the DSEKL step)",
        }
        rec["ok"] = True
        return rec

    cfg = get_config(arch)
    full_rec, meta = _compile_one(arch, shape_name, multi_pod, donate,
                                  variant=variant)
    rec = dict(meta)
    rec["variant"] = variant
    rec["full"] = full_rec

    period, rem, n_p = cfg.period, cfg.n_rem, cfg.n_periods
    p1, _ = _compile_one(arch, shape_name, multi_pod, donate,
                         n_layers=period + rem, unroll=True, variant=variant)
    p2, _ = _compile_one(arch, shape_name, multi_pod, donate,
                         n_layers=2 * period + rem, unroll=True,
                         variant=variant)
    rec["probe1"] = p1
    rec["probe2"] = p2

    def _extra(key, sub):
        a = p1.get(key, {}).get(sub)
        b = p2.get(key, {}).get(sub)
        if a is None or b is None or a < 0 or b < 0:
            return None
        return a + (n_p - 1) * (b - a)

    rec["roofline_inputs"] = {
        "flops": _extra("cost_analysis", "flops"),
        "bytes_accessed": _extra("cost_analysis", "bytes_accessed"),
        "collective_bytes": (
            p1["collectives"]["total_bytes"]
            + (n_p - 1) * (p2["collectives"]["total_bytes"]
                           - p1["collectives"]["total_bytes"])
            if "total_bytes" in p1.get("collectives", {}) else None),
        "collectives_by_op": {
            op: p1["collectives"][op]["bytes"]
            + (n_p - 1) * (p2["collectives"][op]["bytes"]
                           - p1["collectives"][op]["bytes"])
            for op in _COLLECTIVES
            if op in p1.get("collectives", {})},
        "method": "probe-extrapolation (exact per-period linearity)",
    }
    rec["seconds_compile"] = full_rec["seconds_compile"]
    rec["cost_analysis"] = {
        "flops": rec["roofline_inputs"]["flops"],
        "bytes_accessed": rec["roofline_inputs"]["bytes_accessed"]}
    rec["collectives"] = {
        "total_bytes": rec["roofline_inputs"]["collective_bytes"]}
    rec["memory_analysis"] = full_rec.get("memory_analysis", {})
    rec["ok"] = True
    return rec


def cell_path(out_dir: str, arch: str, shape: str, multi_pod: bool,
              variant: str = None) -> str:
    mesh = "2x16x16" if multi_pod else "16x16"
    suffix = f"__{variant}" if variant else ""
    return os.path.join(out_dir, mesh, f"{arch}__{shape}{suffix}.json")


def all_cells():
    for arch in sorted(ARCHS):
        for shape in SHAPES:
            ok, why = applicable(arch, shape)
            if ok:
                yield arch, shape
    # The paper's technique on the same meshes.
    yield "dsekl", "dsekl_covtype"
    yield "dsekl", "dsekl_prod"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default=None,
                    help="named hillclimb variant: " + ",".join(VARIANTS))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args()

    if args.all:
        failures = []
        for multi_pod in (False, True):
            for arch, shape in all_cells():
                path = cell_path(args.out, arch, shape, multi_pod)
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        if json.load(f).get("ok"):
                            continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", args.out]
                if multi_pod:
                    cmd.append("--multi-pod")
                print(f"[dryrun] {arch} x {shape} x "
                      f"{'2x16x16' if multi_pod else '16x16'}", flush=True)
                r = subprocess.run(cmd, timeout=args.timeout)
                if r.returncode != 0:
                    failures.append((arch, shape, multi_pod))
        print(f"[dryrun] sweep done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    path = cell_path(args.out, args.arch, args.shape, args.multi_pod,
                     args.variant)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod,
                       variant=args.variant)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "ok": False, "error": traceback.format_exc()}
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    if rec.get("ok"):
        print(f"[dryrun] OK {args.arch} x {args.shape}: "
              f"flops={rec['cost_analysis'].get('flops', -1):.3e} "
              f"coll={rec['collectives'].get('total_bytes', -1):.3e}B "
              f"compile={rec['seconds_compile']:.1f}s")
        print(json.dumps(rec.get("memory_analysis", {})))
    else:
        print(rec.get("error", "")[-2000:], file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
