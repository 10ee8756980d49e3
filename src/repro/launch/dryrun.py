import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST run before any jax import: jax locks the device count on first init.
os.environ.setdefault("REPRO_UNROLL_SCAN", "1")
# ^^ unroll layer scans so cost_analysis counts every layer's FLOPs and every
#    per-layer collective (a lax.scan body is only counted once by XLA).

"""Multi-pod dry-run (deliverable e) + roofline term extraction (deliverable g).

For every (architecture x input shape) pair this lowers + compiles the
appropriate step function against the production mesh using
ShapeDtypeStruct stand-ins (no allocation):

  * train_4k      -> train_step (loss + grads + Adam update, remat'd)
  * prefill_32k   -> prefill (forward + KV/SSM cache emission)
  * decode_32k /
    long_500k     -> serve_step (ONE token against a seq_len cache)

and records memory_analysis / cost_analysis / HLO-parsed collective bytes
into a JSON that benchmarks/roofline.py turns into EXPERIMENTS.md tables.

Run:  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
          [--multi-pod] [--out benchmarks/results/dryrun.json]
"""

import argparse
import json
import re
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import INPUT_SHAPES, InputShape, ModelConfig, OptimizerConfig
from repro.telemetry import log
from repro.configs import ARCHS, arch_ids, get_config
from repro.launch import shardings as SH
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.models.model import Model, build_model
from repro.optim import init_adam, adam_update

SWA_SERVING_WINDOW = 8192   # ring-KV window for the long_500k dense variant

# (arch, shape) pairs that are skipped, with the documented reason
SKIPS = {
    ("whisper-large-v3", "long_500k"):
        "enc-dec decoder capped at 448 target positions; 524k-token decode "
        "is architecturally meaningless (DESIGN.md §6)",
}


def decode_plan(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """Decide cache capacity / attention window for a decode shape."""
    native_swa = cfg.sliding_window > 0
    if cfg.arch_type == "ssm":
        return {"capacity": 0, "window": 0, "variant": "native-ssm"}
    if shape.name == "long_500k":
        if cfg.arch_type == "hybrid":
            return {"capacity": SWA_SERVING_WINDOW,
                    "window": SWA_SERVING_WINDOW,
                    "variant": "native-ssm+swa-shared-attn"}
        if native_swa:
            return {"capacity": cfg.sliding_window,
                    "window": cfg.sliding_window, "variant": "native-swa"}
        return {"capacity": SWA_SERVING_WINDOW, "window": SWA_SERVING_WINDOW,
                "variant": "swa-serving"}
    # decode_32k
    if native_swa:
        return {"capacity": cfg.sliding_window, "window": cfg.sliding_window,
                "variant": "native-swa"}
    return {"capacity": shape.seq_len, "window": 0, "variant": "full-cache"}


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def input_specs(arch: str, shape_name: str, mesh, *,
                cfg: Optional[ModelConfig] = None,
                ) -> Tuple[Model, Dict[str, Any], Dict[str, Any]]:
    """Returns (model, kwargs-of-SDS for the step fn, plan info)."""
    cfg = cfg or get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    model = build_model(cfg)
    b = shape.global_batch
    plan: Dict[str, Any] = {"kind": shape.kind}

    def tok_sds(bb, ss):
        return jax.ShapeDtypeStruct((bb, ss), jnp.int32)

    extras = {}
    if cfg.arch_type == "vlm":
        from repro.models.vlm import D_PATCH
        extras["patches"] = jax.ShapeDtypeStruct(
            (b, cfg.num_patches, D_PATCH), jnp.dtype(cfg.dtype))
    if cfg.arch_type == "encdec":
        extras["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_seq_len, cfg.d_model), jnp.dtype(cfg.dtype))

    if shape.kind == "train":
        s = shape.seq_len - (cfg.num_patches if cfg.arch_type == "vlm" else 0)
        batch = {"tokens": tok_sds(b, s), "labels": tok_sds(b, s), **extras}
        batch = SH.with_shardings(batch, SH.batch_shardings(batch, mesh))
        plan["tokens_per_step"] = shape.seq_len * b
        return model, {"batch": batch}, plan

    if shape.kind == "prefill":
        s = shape.seq_len - (cfg.num_patches if cfg.arch_type == "vlm" else 0)
        batch = {"tokens": tok_sds(b, s), **extras}
        batch = SH.with_shardings(batch, SH.batch_shardings(batch, mesh))
        plan["capacity"] = shape.seq_len
        plan["tokens_per_step"] = shape.seq_len * b
        return model, {"batch": batch}, plan

    # decode
    dp = decode_plan(cfg, shape)
    plan.update(dp)
    cap = dp["capacity"]
    cache_shape = jax.eval_shape(lambda: model.init_cache(b, max(cap, 1)))
    cache = SH.with_shardings(cache_shape,
                              SH.cache_shardings(cache_shape, mesh))
    tokens = jax.ShapeDtypeStruct((b,), jnp.int32)
    plan["tokens_per_step"] = b
    return model, {"cache": cache, "tokens": tokens}, plan


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def make_step_fn(model: Model, kind: str, plan: Dict[str, Any], mesh):
    ocfg = OptimizerConfig()
    if kind == "train":
        def train_step(params, opt_state, batch):
            def loss_fn(p):
                loss, m = model.loss(p, batch, remat=True)
                return loss
            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, opt_state, _ = adam_update(ocfg, params, grads, opt_state)
            return params, opt_state, loss
        return train_step, True
    if kind == "prefill":
        def prefill_step(params, batch):
            return model.prefill(params, batch, plan["capacity"])
        return prefill_step, False
    # decode
    window = plan["window"]

    def serve_step(params, cache, tokens):
        return model.decode_step(params, cache, tokens, window=window)
    return serve_step, False


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_COLL_RE = re.compile(
    r"^\s*(?:%[\w.\-]+|ROOT [\w.\-%]+)?\s*=\s*(.*?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[-a-z]*\(", re.M)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred"
                       r"|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def collective_bytes(hlo: str) -> Dict[str, float]:
    """Per-device bytes moved by each collective family (from optimized HLO)."""
    out: Dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo):
        lhs, op = m.group(1), m.group(2)
        nbytes = 0
        for sm in _SHAPE_RE.finditer(lhs):
            dt, dims = sm.group(1), sm.group(2)
            numel = 1
            for d in dims.split(","):
                if d:
                    numel *= int(d)
            nbytes += numel * _DTYPE_BYTES[dt]
        out[op] = out.get(op, 0) + nbytes
    return out


# ---------------------------------------------------------------------------
# cost analysis helpers
# ---------------------------------------------------------------------------

def _build_args(arch: str, shape_name: str, mesh, cfg=None):
    """(model, args-SDS list, plan) for the step fn of this pair."""
    model, kwargs, plan = input_specs(arch, shape_name, mesh, cfg=cfg)
    step_fn, needs_opt = make_step_fn(model, plan["kind"], plan, mesh)
    params_shape = jax.eval_shape(partial(model.init), jax.random.PRNGKey(0))
    p_sds = SH.with_shardings(params_shape,
                              SH.param_shardings(params_shape, mesh))
    args = [p_sds]
    if needs_opt:
        opt_shape = jax.eval_shape(init_adam, params_shape)
        from repro.optim.adam import OptState
        o_sds = OptState(
            SH.with_shardings(opt_shape.m,
                              SH.param_shardings(opt_shape.m, mesh)),
            SH.with_shardings(opt_shape.v,
                              SH.param_shardings(opt_shape.v, mesh)),
            jax.ShapeDtypeStruct((), jnp.int32))
        args.append(o_sds)
    if "batch" in kwargs:
        args.append(kwargs["batch"])
    else:
        args.extend([kwargs["cache"], kwargs["tokens"]])
    return model, step_fn, args, plan


def _unrolled_cost(arch: str, shape_name: str, mesh, cfg) -> Tuple[
        float, float, Dict[str, float]]:
    """(flops/dev, bytes/dev, collective-bytes/dev) of the UNROLLED program."""
    _, step_fn, args, _ = _build_args(arch, shape_name, mesh, cfg=cfg)
    os.environ["REPRO_UNROLL_SCAN"] = "1"
    with mesh:
        compiled = jax.jit(lambda *a: step_fn(*a)).lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    colls = collective_bytes(compiled.as_text())
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)), colls)


def cost_terms(arch: str, shape_name: str, mesh, cfg) -> Tuple[
        float, float, Dict[str, float], str]:
    """FLOPs / bytes / collective bytes per device for the full-depth model.

    Dense/MoE/encdec/VLM towers unroll fully (exact).  SSM/hybrid towers
    blow up XLA's optimizer when unrolled at depth 48-54 x seq-chunk scans
    (>30 min/pair compile), so their cost is measured at two reduced depths
    and extrapolated linearly — exact for homogeneous layers, since
    per-layer cost is depth-independent:
        per_layer = (X(L2) - X(L1)) / (L2 - L1);  X(L) = X(L1) + per*(L-L1)
    For zamba2 the depth unit is one SEGMENT (attn_every mamba layers + the
    shared attention application), preserving the mixture.
    """
    deep = cfg.num_layers + cfg.num_encoder_layers >= 48
    # XLA's optimizer blows up past ~50 unrolled bodies at these sizes
    if cfg.arch_type not in ("ssm", "hybrid") and not deep:
        f, b, c = _unrolled_cost(arch, shape_name, mesh, cfg)
        return f, b, c, "unrolled-full"
    if cfg.arch_type == "hybrid":
        unit = cfg.attn_every
    elif cfg.arch_type == "ssm":
        unit = 2
    else:
        unit = 4
    l1, l2, L = unit, 2 * unit, cfg.num_layers

    def variant(l):
        kw = {"num_layers": l}
        if cfg.arch_type == "encdec":   # scale both towers together
            kw["num_encoder_layers"] = max(
                cfg.num_encoder_layers * l // cfg.num_layers, 1)
        return cfg.replace(**kw)

    f1, b1, c1 = _unrolled_cost(arch, shape_name, mesh, variant(l1))
    f2, b2, c2 = _unrolled_cost(arch, shape_name, mesh, variant(l2))
    scale = (L - l1) / (l2 - l1)
    f = f1 + (f2 - f1) * scale
    b = b1 + (b2 - b1) * scale
    colls = {k: c1.get(k, 0.0) + (c2.get(k, 0.0) - c1.get(k, 0.0)) * scale
             for k in set(c1) | set(c2)}
    return f, b, colls, f"unrolled-extrapolated({l1}->{l2}->{L})"


# ---------------------------------------------------------------------------
# single dry-run
# ---------------------------------------------------------------------------

def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            with_cost: bool = True, verbose: bool = True,
            lower_only: bool = False) -> Dict[str, Any]:
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if multi_pod else "16x16"}
    if (arch, shape_name) in SKIPS:
        rec["status"] = "skipped"
        rec["reason"] = SKIPS[(arch, shape_name)]
        return rec
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 512 if multi_pod else 256
    t0 = time.time()
    try:
        # --- pass 1: deployment-shaped program (layer scans) -> memory ----
        os.environ["REPRO_UNROLL_SCAN"] = "0"
        model, step_fn, args, plan = _build_args(arch, shape_name, mesh)
        with mesh:
            # fresh closure each pass — the env flag is read at trace time and
            # jax caches jaxprs by function identity
            lowered = jax.jit(lambda *a: step_fn(*a)).lower(*args)
            t1 = time.time()
            if lower_only:
                # --smoke: mesh construction + lowering proof only (the CI
                # guard against mesh API regressions; no compile / cost)
                rec.update({"status": "lowered",
                            "lower_s": round(t1 - t0, 1)})
                if verbose:
                    log(f"[ok] {arch:22s} {shape_name:12s} "
                          f"{rec['mesh']:8s} lowered in "
                          f"{rec['lower_s']:6.1f}s (smoke)")
                return rec
            compiled = lowered.compile()
            t2 = time.time()
        ma = compiled.memory_analysis()

        # --- pass 2: unrolled layers -> per-layer FLOPs + collectives -----
        # (XLA counts a while-loop body once, so cost_analysis on the scan
        #  program would understate compute/collective terms by ~num_layers;
        #  conversely the unrolled program confuses buffer liveness, so the
        #  memory analysis comes from the scan program.)
        if with_cost:
            flops_dev, bytes_dev, colls, cost_mode = cost_terms(
                arch, shape_name, mesh, cfg)
        else:  # multi-pod pass: lower+compile proof only (roofline is
            #    single-pod — see DESIGN.md §7)
            flops_dev, bytes_dev, colls, cost_mode = 0.0, 0.0, {}, "skipped"
        coll_dev = float(sum(colls.values()))
        compute_s = flops_dev / PEAK_FLOPS_BF16
        memory_s = bytes_dev / HBM_BW
        coll_s = coll_dev / ICI_BW
        dominant = max((("compute", compute_s), ("memory", memory_s),
                        ("collective", coll_s)), key=lambda kv: kv[1])[0]

        n_active = cfg.active_param_count()
        tokens = plan["tokens_per_step"]
        mult = 6 if plan["kind"] == "train" else 2
        model_flops = mult * n_active * tokens
        hlo_flops_global = flops_dev * chips

        rec.update({
            "status": "ok",
            "variant": plan.get("variant", ""),
            "cost_mode": cost_mode,
            "lower_s": round(t1 - t0, 1),
            "compile_s": round(t2 - t1, 1),
            "memory": {
                "argument_B": ma.argument_size_in_bytes,
                "output_B": ma.output_size_in_bytes,
                "temp_B": ma.temp_size_in_bytes,
                "alias_B": ma.alias_size_in_bytes,
                "peak_est_B": ma.argument_size_in_bytes +
                ma.output_size_in_bytes + ma.temp_size_in_bytes -
                ma.alias_size_in_bytes,
            },
            "cost": {"flops_per_dev": flops_dev,
                     "bytes_per_dev": bytes_dev},
            "collectives_B_per_dev": colls,
            "roofline": {
                "compute_s": compute_s,
                "memory_s": memory_s,
                "collective_s": coll_s,
                "dominant": dominant,
                "model_flops": model_flops,
                "hlo_flops_global": hlo_flops_global,
                "useful_ratio": (model_flops / hlo_flops_global
                                 if hlo_flops_global else 0.0),
            },
        })
        if verbose:
            mb = rec["memory"]["peak_est_B"] / 2**30
            log(f"[ok] {arch:22s} {shape_name:12s} {rec['mesh']:8s} "
                  f"compile {rec['compile_s']:6.1f}s mem/dev {mb:7.2f}GiB "
                  f"c/m/coll {compute_s:.2e}/{memory_s:.2e}/{coll_s:.2e}s "
                  f"dom={dominant} useful={rec['roofline']['useful_ratio']:.2f}")
    except Exception as e:   # noqa: BLE001 — record failures in the report
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            log(f"[ERR] {arch} {shape_name}: {rec['error'][:200]}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-cost", action="store_true",
                    help="skip the unrolled cost pass (lower+compile proof "
                         "only — the default for the multi-pod sweep)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mesh-regression guard: construct every "
                         "production/pipeline mesh variant and lower one "
                         "small training pair (no compile, no cost pass) — "
                         "fails fast when the installed jax changes how "
                         "jax.make_mesh / jax.sharding.AxisType build a "
                         "mesh")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    if args.smoke:
        from repro.launch.mesh import make_pipeline_mesh
        for mp in (False, True):
            prod = make_production_mesh(multi_pod=mp)
            pipe = make_pipeline_mesh(num_stages=8, multi_pod=mp)
            log(f"[mesh ok] multi_pod={mp} production={dict(prod.shape)} "
                  f"pipeline={dict(pipe.shape)}")
        rec = run_one("paper-llama-124m", "train_4k", lower_only=True)
        if rec["status"] != "lowered":
            log(str(rec.get("error", rec)))
            raise SystemExit(1)
        log("=== mesh smoke OK ===")
        return

    archs = arch_ids() if args.arch == "all" else args.arch.split(",")
    shapes = (list(INPUT_SHAPES) if args.shape == "all"
              else args.shape.split(","))
    results = []
    for arch in archs:
        for shape in shapes:
            results.append(run_one(arch, shape, multi_pod=args.multi_pod,
                                   with_cost=not args.no_cost))
            if args.out:   # incremental write (runs are long)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    err = sum(r["status"] == "error" for r in results)
    log(f"\n=== dry-run complete: {ok} ok / {sk} skipped / {err} errors "
          f"over {len(results)} pairs ===")
    if err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
