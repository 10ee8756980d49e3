"""Run the CheckFree trainer on a TPU at full ``paper-llama-124m`` width and
check what comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the SPMD pipeline over four chips

One chip, four phases, all in this one process (a chip belongs to one
process at a time):

* ``plain``      — the training CLI (``repro.launch.train.main``) with
  ``checkfree_plus``, 8 steps, fuse window 4;
* ``recovery``   — the ``Trainer`` API, ``checkfree_plus``, 12 steps, a
  middle stage (1) failing at wall step 3 and an edge stage (0) at 7;
* ``merge``      — Alg. 1's weighted average of a middle stage, on the chip
  (plain jnp and the compiled ``stage_merge`` kernel), against the same
  average taken in NumPy float32 from the neighbours;
* ``checkpoint`` — the checkpoint baseline saving every 4 steps, with one
  failure after the first save, so it rolls back and replays.

``--four-chips`` runs only the pipeline-parallel backend
(``Trainer(backend="spmd")``, one stage per chip) beside the host backend
on one of the chips, under ``checkfree`` and ``checkfree_plus`` failures,
and checks that both give the same failures and agree on the loss curve
and the recovery errors.

Weights are random (fixed seeds) and the data is the repo's synthetic
stream.  Each phase prints its steps, failures, first and last loss,
recovery errors, compile seconds and steady window seconds: one
unrepeated run each, not a benchmark.  Any failed phase exits non-zero;
only when every phase passed is the last line of stdout the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a TPU
(e.g. under ``JAX_PLATFORMS=cpu``) the script stops at once.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# loss curves of the two backends in bfloat16: the pipeline splits the
# batch into microbatches and sums gradients per stage, so the two differ
# by rounding only — a few bf16 ulps (2**-8 relative) after six steps
FOUR_CHIP_LOSS_RTOL = 1e-2
# recovery errors are squared distances between merged and lost weights,
# whose merge weights are the (bf16-rounded) gradient square norms
FOUR_CHIP_RECOVERY_RTOL = 5e-2


class ForcedSchedule:
    """Failures at fixed wall steps: ``{wall_step: [stage, ...]}``."""

    def __init__(self, events):
        self._events = dict(events)

    def at(self, step):
        return self._events.get(step, [])


class CompileClock:
    """Seconds the backend spends compiling, and how many programs it
    compiled, from JAX's own monitoring events (tracing and lowering are
    not counted: their events nest)."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self._EVENT:
            self.seconds += duration
            self.programs += 1

    def reading(self):
        return self.seconds, self.programs


def window_times(spans):
    """Per window size: the first window's seconds (which include the
    compile) and the median of the later ones (dispatch plus the
    ``device_get`` drain), from the trainer's telemetry spans."""
    dispatch = [s for s in spans if s["name"] == "window_dispatch"]
    drain = [s for s in spans if s["name"] == "window_drain"]
    by_k = {}
    for d, r in zip(dispatch, drain):
        by_k.setdefault(d["args"]["k"], []).append(
            (d["dur_us"] + r["dur_us"]) / 1e6)
    return {k: {"windows": len(v), "first_s": v[0],
                "steady_s": float(np.median(v[1:])) if len(v) > 1 else None}
            for k, v in sorted(by_k.items())}


def check_finite(name, values):
    values = np.asarray(values, np.float64)
    if values.size == 0 or not np.all(np.isfinite(values)):
        raise AssertionError(f"{name} not all finite: {values.tolist()}")


def summarize(hist, *, recovery_finite=True):
    check_finite("loss", hist.loss)
    errors = [e for _, e in hist.recovery_errors]
    if recovery_finite and errors:
        check_finite("recovery errors", errors)
    return {"steps": hist.steps[-1] if hist.steps else 0,
            "wall_iters": hist.wall_iters,
            "failures": [list(f) for f in hist.failures],
            "first_loss": hist.loss[0], "last_loss": hist.loss[-1],
            "loss": hist.loss, "recovery_errors": errors}


def train_config(cfg, *, strategy, steps, batch, seq, microbatch=None,
                 eval_every=10 ** 6, **recovery):
    from repro.config import OptimizerConfig, RecoveryConfig, TrainConfig
    rcfg = RecoveryConfig(strategy=strategy, num_stages=4, **recovery)
    return TrainConfig(
        global_batch=batch, microbatch=microbatch or batch, seq_len=seq,
        steps=steps, eval_every=eval_every, fuse_window=4,
        optimizer=OptimizerConfig(lr=3e-4, total_steps=steps,
                                  warmup_steps=2),
        recovery=rcfg)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_plain(tmp, cfg, batch, seq):
    """The training CLI in-process, as a user would start it."""
    from repro.core.state import History
    from repro.launch import train
    out = os.path.join(tmp, "plain_history.json")
    argv = ["--arch", cfg.name, "--strategy", "checkfree_plus",
            "--steps", "8", "--fuse-window", "4", "--batch", str(batch),
            "--seq", str(seq), "--quiet", "--out", out]
    train.main(argv)
    with open(out) as f:
        hist = History.from_json(f.read())
    res = summarize(hist)
    if res["steps"] != 8:
        raise AssertionError(f"plain run ended at step {res['steps']}")
    check_finite("eval loss", [e for _, _, e in hist.eval_loss])
    res["argv"] = " ".join(argv[:-2])
    return res


def phase_recovery(tmp, cfg, batch, seq):
    """checkfree_plus through the Trainer API: a middle and an edge stage
    fail and are rebuilt from their neighbours."""
    from repro.core.trainer import Trainer
    from repro.data.pipeline import SyntheticLM, batch_for, make_batches
    from repro.models.model import build_model
    events = {3: [1], 7: [0]}
    tcfg = train_config(cfg, strategy="checkfree_plus", steps=12,
                        batch=batch, seq=seq, eval_every=12)
    trainer = Trainer(build_model(cfg), tcfg, schedule=ForcedSchedule(events))
    src = SyntheticLM(cfg.vocab_size, seed=1234)
    evals = [batch_for(cfg, src.sample(np.random.default_rng(999), batch,
                                       seq))]
    state, hist = trainer.run(make_batches(cfg, batch=batch, seq=seq,
                                           seed=0), evals)
    res = summarize(hist)
    want = [[s, st] for s, sts in sorted(events.items()) for st in sts]
    if res["failures"] != want or len(res["recovery_errors"]) != 2:
        raise AssertionError(f"failures {res['failures']} != {want}")
    if not all(e > 0 for e in res["recovery_errors"]):
        raise AssertionError(f"recovery errors {res['recovery_errors']}")
    if state.effective_step != 12:
        raise AssertionError(f"ended at step {state.effective_step}")
    check_finite("eval loss", [e for _, _, e in hist.eval_loss])
    res["eval_loss"] = hist.eval_loss[-1][2]
    res["window_sizes"] = sorted(trainer.dispatched_buckets)
    return res


def phase_merge(tmp, cfg, batch, seq):
    """Alg. 1 on the chip vs NumPy float32, on the initial parameters."""
    import jax
    import jax.numpy as jnp
    from repro.core.recovery import recover_stage
    from repro.core.stages import StagePartition
    from repro.models.model import build_model
    part = StagePartition(cfg, 4)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    omegas = np.array([1.0, 3.0, 0.5, 2.0], np.float32)
    host = jax.device_get(params)
    prev, nxt = part.get_stage(host, 0), part.get_stage(host, 2)
    w0, w2 = omegas[0], omegas[2]
    want = jax.tree.map(lambda a, b: (w0 * a + w2 * b) / (w0 + w2), prev, nxt)
    res = {}
    for use_kernel in (False, True):
        out = jax.device_get(recover_stage(params, part, 1,
                                           jnp.asarray(omegas),
                                           strategy="grad_norm",
                                           use_kernel=use_kernel))
        rel = max(float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
                  for g, w in zip(jax.tree.leaves(part.get_stage(out, 1)),
                                  jax.tree.leaves(want)))
        if not rel <= 1e-6:
            raise AssertionError(f"merge (kernel={use_kernel}) off by "
                                 f"{rel:.3e} relative")
        for s in (0, 2, 3):
            for a, b in zip(jax.tree.leaves(part.get_stage(out, s)),
                            jax.tree.leaves(part.get_stage(host, s))):
                if not np.array_equal(a, b):
                    raise AssertionError(f"stage {s} changed by the merge")
        res["kernel" if use_kernel else "jnp"] = {"max_rel_err": rel}
    return res


def phase_checkpoint(tmp, cfg, batch, seq):
    """The checkpoint baseline: save at step 4, fail at wall step 6, roll
    back to 4 and replay."""
    from repro.core.trainer import Trainer
    from repro.data.pipeline import make_batches
    from repro.models.model import build_model
    tcfg = train_config(cfg, strategy="checkpoint", steps=8, batch=batch,
                        seq=seq, checkpoint_every=4,
                        checkpoint_dir=os.path.join(tmp, "ckpt"),
                        store_dir=os.path.join(tmp, "store"))
    trainer = Trainer(build_model(cfg), tcfg,
                      schedule=ForcedSchedule({6: [1]}))
    state, hist = trainer.run(make_batches(cfg, batch=batch, seq=seq,
                                           seed=0))
    res = summarize(hist, recovery_finite=False)
    if res["failures"] != [[6, 1]] or state.effective_step != 8 \
            or hist.wall_iters != 10:
        raise AssertionError(
            f"rollback: failures {res['failures']}, effective step "
            f"{state.effective_step}, wall iterations {hist.wall_iters}")
    # steps 5 and 6 ran twice: before the failure and after the rollback
    first, replay = hist.loss[4:6], hist.loss[6:8]
    if hist.steps[4:8] != [5, 6, 5, 6]:
        raise AssertionError(f"replayed steps {hist.steps}")
    diff = float(np.max(np.abs(np.subtract(first, replay))))
    if not diff <= 1e-3 * abs(first[0]):
        raise AssertionError(f"replay diverged: {first} vs {replay}")
    res["replay_max_abs_diff"] = diff
    return res


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def check_tower_sharded(params, devices):
    """Every chip holds its own contiguous slice of the block tower."""
    import jax
    want = set(devices)
    for leaf in jax.tree.leaves(params["blocks"]):
        shards = leaf.addressable_shards
        held = {s.device for s in shards}
        starts = sorted(s.index[0].start or 0 for s in shards)
        per = leaf.shape[0] // len(devices)
        if held != want or starts != [i * per for i in range(len(devices))]:
            raise AssertionError(
                f"tower leaf {leaf.shape} held on {held}, slices {starts}")


def phase_four_chips(tmp, cfg, batch, seq):
    import jax
    from repro.core.trainer import Trainer
    from repro.data.pipeline import make_batches
    from repro.models.model import build_model
    devices = jax.devices()[:4]
    res = {}
    # as tests/pipeline_spmd_check.py: checkfree loses a middle stage,
    # checkfree_plus a middle and an edge one
    for strategy, events in (("checkfree", {3: [2]}),
                             ("checkfree_plus", {2: [0], 4: [2]})):
        runs = {}
        for backend in ("host", "spmd"):
            tcfg = train_config(cfg, strategy=strategy, steps=6, batch=batch,
                                seq=seq, microbatch=batch // 2)
            trainer = Trainer(build_model(cfg), tcfg,
                              schedule=ForcedSchedule(events),
                              backend=backend)
            rec = _recorder()
            state, hist = trainer.run(make_batches(cfg, batch=batch,
                                                   seq=seq, seed=0))
            if backend == "spmd":
                if set(trainer.mesh.devices.flat) != set(devices):
                    raise AssertionError(f"mesh {trainer.mesh.devices}")
                check_tower_sharded(state.params, devices)
            else:
                held = {d for leaf in jax.tree.leaves(state.params)
                        for d in leaf.devices()}
                if held != {devices[0]}:
                    raise AssertionError(f"host backend on {held}")
            runs[backend] = dict(summarize(hist),
                                 windows=window_times(rec.spans))
        host, spmd = runs["host"], runs["spmd"]
        if host["failures"] != spmd["failures"]:
            raise AssertionError(f"{strategy}: failures {host['failures']} "
                                 f"!= {spmd['failures']}")
        loss_rel = float(np.max(
            np.abs(np.subtract(host["loss"], spmd["loss"]))
            / np.abs(host["loss"])))
        rec_rel = float(np.max(np.abs(np.subtract(
            host["recovery_errors"], spmd["recovery_errors"]))
            / np.abs(host["recovery_errors"])))
        if not (loss_rel <= FOUR_CHIP_LOSS_RTOL
                and rec_rel <= FOUR_CHIP_RECOVERY_RTOL):
            raise AssertionError(
                f"{strategy}: loss max rel diff {loss_rel:.3e} (limit "
                f"{FOUR_CHIP_LOSS_RTOL}), recovery error max rel diff "
                f"{rec_rel:.3e} (limit {FOUR_CHIP_RECOVERY_RTOL})")
        res[strategy] = {"host": host, "spmd": spmd,
                         "loss_max_rel_diff": loss_rel,
                         "recovery_max_rel_diff": rec_rel}
    return res


# ---------------------------------------------------------------------------
# running the phases
# ---------------------------------------------------------------------------

def _recorder():
    """A fresh in-memory telemetry recorder, installed process-wide, so the
    trainer's window spans can be read back."""
    from repro import telemetry
    from repro.telemetry.recorder import Recorder
    rec = Recorder(None)
    telemetry.set_recorder(rec)
    return rec


def run_phase(name, fn, tmp, cfg, batch, seq, clock):
    import jax
    c0, p0 = clock.reading()
    t0 = time.perf_counter()
    rec = _recorder()
    try:
        res = fn(tmp, cfg, batch, seq)
    except Exception:  # noqa: BLE001 — report every phase, then fail
        traceback.print_exc()
        print(f"[{name}] FAILED", flush=True)
        return False
    c1, p1 = clock.reading()
    res["compile_s"] = c1 - c0
    res["programs_compiled"] = p1 - p0
    res["phase_s"] = time.perf_counter() - t0
    if rec.spans:
        res["windows"] = window_times(rec.spans)
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        res["peak_hbm_gib_so_far"] = stats["peak_bytes_in_use"] / 2 ** 30
    print(f"[{name}] ok {json.dumps(res, default=float)}", flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD pipeline over four chips, "
                         "beside the host backend on one of them")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    need = 4 if args.four_chips else 1
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {len(devices)} "
              f"{dev.platform} device(s)); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < need:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.configs.paper_llama import SMALL
    from repro.launch.compile_cache import configure_compile_cache
    cache = configure_compile_cache()
    cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    clock = CompileClock()
    print(f"chip_smoke on {len(devices)} x {dev.device_kind} "
          f"({dev.platform}), jax {jax.__version__}, compile cache {cache} "
          f"({cached} entries at start); "
          f"model {SMALL.name} at full width, batch 8 x seq 512, random "
          "weights; each phase is one unrepeated run, not a benchmark",
          flush=True)

    if args.four_chips:
        phases = [("four_chips", phase_four_chips)]
    else:
        phases = [("plain", phase_plain), ("recovery", phase_recovery),
                  ("merge", phase_merge), ("checkpoint", phase_checkpoint)]
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        ok = all([run_phase(name, fn, tmp, SMALL, 8, 512, clock)
                  for name, fn in phases])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
        from repro import telemetry
        telemetry.set_recorder(None)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
