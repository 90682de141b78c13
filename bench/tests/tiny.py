"""Tiny stand-ins of the benchmark's cells for CPU tests: each cell's own
configuration and traffic files, cut to sizes a test run holds."""

import copy
import math

from bench import harness

# a made-up peak for the CPU backend, so that the readers run; numbers from
# a CPU run are never device metrics
PEAKS = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}

ARCH = {
    "smollm-chat": dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                        head_dim=16, d_ff=128, vocab_size=256),
    "granite-code-tp4": dict(num_layers=2, d_model=64, num_heads=8,
                             num_kv_heads=4, head_dim=8, d_ff=128,
                             vocab_size=256),
}
SECONDS = 1.5


def cell(name: str, mesh=(1, 1), **arch):
    """(manifest, configuration, traffic) of cell ``name`` at a tiny size."""
    man = harness.manifest()
    _, conf, traffic = harness.cell(name, man)
    conf = copy.deepcopy(conf)
    conf["arch"].update(ARCH[name], **arch)
    conf.update(slots=4, max_len=128, mesh=list(mesh), chips=math.prod(mesh))
    conf["check"].update(compared_tokens=20, reference_tokens=2000)
    traffic = copy.deepcopy(traffic)
    lens = dict(prompt_len=dict(median=20, min=8, max=60),
                output_len=dict(median=8, min=4, max=16))
    for k, v in lens.items():
        traffic[k].update(v)
    if traffic["loop"] == "open":
        traffic["rate_per_s"] = 8.0
    else:
        traffic.update(waiting=4, pool=16)
    return man, conf, traffic


def valid(result: dict, listed) -> list:
    """Faults of a result line: a listed metric missing, not finite, not
    above 0, or a share above 100%."""
    out = []
    for m in listed:
        got = result["metrics"].get(m["name"])
        if got is None:
            out.append(f"{m['name']} missing")
            continue
        v = got["value"]
        if not (math.isfinite(v) and v > 0):
            out.append(f"{m['name']} = {v}")
        elif m["unit"] == "%" and v > 100:
            out.append(f"{m['name']} = {v}")
    return out
