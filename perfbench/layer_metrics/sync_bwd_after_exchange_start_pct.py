"""Where the compiler put the sums: per traced step, of the `backward` +
`remat` device time, the share that lies after the step's first collective
under `ps.exchange` has started; mean over the steps, on the chip where it is lowest.  0 = every
sum waits for the whole backward; the larger, the more backward work there is
for a sum to hide behind."""
from perfbench.layer_metrics._sync_phases import bwd_after_exchange_start_pct


def read(obs):
    return bwd_after_exchange_start_pct(obs)
