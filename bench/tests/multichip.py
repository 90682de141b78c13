"""Drive the four-chip cell at a tiny size on four virtual CPU devices and
print one JSON line per run (used by ``test_multichip.py`` in a process of
its own, since the device count is fixed when JAX starts).

    python -m bench.tests.multichip <out_dir> <seed> [<seed> ...]
"""

import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"


def main(out_dir, seeds):
    import json
    from pathlib import Path
    from bench import harness
    from bench.tests import tiny
    cell = "granite-code-tp4"
    man, conf, traffic = tiny.cell(cell, mesh=(1, 4))
    sess = harness.Session(conf, traffic, seeds[0])
    for trace in (0, 1):
        for seed in seeds:
            out = harness.run(cell, seed, tiny.SECONDS, bool(trace), man=man,
                              conf=conf, traffic=traffic,
                              peak_table=tiny.PEAKS, out_dir=Path(out_dir),
                              session=sess)
            print(json.dumps({"seed": seed, "trace": trace, **out}),
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
