"""Share of the window the step loop spent getting the next batch (the
benchmark's `next_batch` span round `next(feed)`: the loader's queue, or the
draw and `lm_batch`), in percent of the window."""


def read(obs):
    lo, hi = obs["result"]["window"]
    spans = obs["spans"]
    if not any(n == "next_batch" for n, _, _ in spans.records):
        return None
    return 100.0 * spans.total("next_batch", lo, hi) / (hi - lo)
