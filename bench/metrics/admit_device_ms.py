"""Device time of admission's two programs, per admission of the slice,
averaged over the chips: the batched scatter of the prefilled cache into
the slots, and the slot-state update. Each chip must show one execution of
each per admission the harness recorded in the slice."""

from bench import readings
from bench import trace as trace_lib

# each program's XLA module, under the name the engine gives it
# (``admit_scatter``, ``admit_state``) and the one it had before
# (``batched_scatter``, ``_admit_state``)
PROGRAMS = (("jit_admit_scatter", "jit_batched_scatter"),
            ("jit_admit_state", "jit__admit_state"))


def read(rec):
    n = len(readings.slice_prefills(rec))
    devs = readings.devices(rec)
    if not devs or not n:
        return None
    total = 0.0
    for d in devs:
        for names in PROGRAMS:
            ex = [e for name in names for e in trace_lib.executions(d, name)]
            if len(ex) != n:
                raise ValueError(
                    f"trace holds {len(ex)} executions of {names[0]} on a "
                    f"device, the slice admitted {n} batches")
            total += sum(b - a for a, b in ex)
    return 1e3 * total / len(devs) / n
