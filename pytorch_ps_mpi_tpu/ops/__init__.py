"""The operations the models and the step are built from, a module each:

* `codecs`, `pallas_kernels`, `robust`: gradient codecs, their Pallas
  kernels, robust aggregation.
* `flash_attention`: `flash_attention(q, k, v, causal=, scale=, window=,
  return_lse=)` and `tile_plan`, the Mosaic attention kernels.
* `eva_attention`: `eva_attention(q, k, v, phi, mu, window=, chunk=)`,
  EvaByte's two-set softmax (chunk summaries, the flash kernels over the
  windows, the exact join on the row statistics) and its plain form.
* `kda`, `kda_pallas`: `kda_attention`, the KDA recurrence (chunked plain
  form and Pallas kernels).
* `selective_scan`: `selective_scan(x, dt, A, B, C, D)`, the Mamba-1
  state-space scan (blocked plain form, hand-written backward).
* `ssd`, `ssd_pallas`: `ssd(x, dt, A, B, C, D, chunk=)`, Mamba-2's
  state-space duality scan (chunked plain form and Pallas kernels).

Nothing is imported here: a program that never attends does not load Pallas.
"""

__all__ = ["codecs", "eva_attention", "flash_attention", "kda", "kda_pallas", "pallas_kernels",
           "robust", "selective_scan", "ssd", "ssd_pallas"]
