"""Drive the harness at a tiny size on the CPU, skipping only its look for
a chip, without the persistent compilation cache."""
import time

from bench import harness
from tiny import tiny_spec


def run_tiny(monkeypatch, config, mix, seed, limits=None):
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "configure_compile_cache", lambda: None)
    return harness.run(tiny_spec(config, mix, limits), seed=seed,
                       seconds=0.5, trace=False, started=time.perf_counter(),
                       require_chip=False)
