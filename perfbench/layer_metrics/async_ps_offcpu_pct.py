"""Share of the PS thread's working spans (`async.stack`, `.apply`,
`.publish`, `.read_loss`; not `.fill`, where waiting is the job) during which
the thread was off the CPU: 100 x (1 - thread CPU seconds / wall seconds),
summed over the window.  Off the CPU is waiting: for the GIL, a lock, or the
device inside the runtime."""
from perfbench.layer_metrics._async_spans import (PS_WORK, seconds,
                                                  window_records)


def read(obs):
    work = [r for r in window_records(obs) or () if r["name"] in PS_WORK]
    wall = sum(seconds(r) for r in work)
    if not wall:
        return None
    return 100.0 * (1.0 - sum(r["cpu"] for r in work) / wall)
