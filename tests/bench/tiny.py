"""Tiny cells for the CPU tests: the real configuration files with every
size cut so a CPU run takes seconds, and limits wide enough for sound
runs at that size."""
import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def tiny_config(name):
    cfg = copy.deepcopy(_load(f"bench/configs/{name}.json"))
    m, t = cfg["model"], cfg["train"]
    if cfg["reference"] == "moe_transformer":
        m.update(num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
                 head_dim=16, vocab_size=128)
        m["moe"].update(num_experts=4, top_k=2, d_ff_expert=32,
                        group_tokens=16)
    else:
        m.update(num_layers=8, d_model=64, vocab_size=128)
        m["ssm"].update(state_dim=16, head_dim=16, chunk_size=8)
    t.update(global_batch=4, microbatch=4, seq_len=16)
    # float32 compute, so that the program reads rounding only and the
    # control one step below (bfloat16) stands well apart
    m["dtype"] = "float32"
    return cfg


#: limits for these sizes on the CPU, set between what sound float32 runs
#: read (loss, gradient and omega gaps about 1e-7, the change 2e-4 at most)
#: and what the bfloat16 control reads (loss 4e-5, gradient 3e-3, change
#: 3e-3, omega 7e-4 at least)
TINY_LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-4, "grad_diff": 1e-3,
               "change_gap": 1e-3,
               "omega_gap": 1e-4, "recovery_gap": 1e-5,
               "untouched_moved": 0.0, "lost_moments": 0.0,
               "lr_boost_gap": 0.0}


def tiny_spec(config_name, traffic_name, limits=None):
    traffic = _load(f"bench/traffic/{traffic_name}.json")
    lim = dict(TINY_LIMITS if limits is None else limits)
    if not traffic.get("fail_every"):
        for k in ("recovery_gap", "untouched_moved", "lost_moments",
                  "lr_boost_gap"):
            lim.pop(k, None)
    bench = _load("BENCHMARK.json")
    return {"name": f"tiny.{traffic_name}", "chips": 1,
            "config": tiny_config(config_name), "traffic": traffic,
            "limits": lim, "end_to_end": bench["end_to_end"],
            "per_layer": bench["per_layer"]}
