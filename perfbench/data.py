"""Seeded traffic for the benchmark: the one general generator.

The pools are copies of the program's own seeded generators
(`pytorch_ps_mpi_tpu/data/datasets.py`: `synthetic_lm`,
`synthetic_classification`; `async_ps.dataset_batch_fn`), kept here so that
a later PR cannot change the traffic by changing the program.  A cell's
`feed` group (a data file under `perfbench/workloads/`) names the kind of
feed and its parameters; nothing here knows a cell by name.
"""

from __future__ import annotations

from typing import Callable, Iterator

import numpy as np


def token_pool(rows: int, seq_len: int, vocab: int, seed: int,
               noise: float = 0.02) -> np.ndarray:
    """Token rows ``[rows, seq_len + 1]`` following the affine recurrence
    t+1 = 5t + 3 (mod vocab) with a little noise, so that a language model's
    loss falls within a few steps."""
    rng = np.random.RandomState(seed)
    cols = [rng.randint(0, vocab, size=(rows, 1))]
    for _ in range(seq_len):
        cols.append((cols[-1] * 5 + 3) % vocab)
    toks = np.concatenate(cols, axis=1)
    flip = rng.rand(*toks.shape) < noise
    toks[flip] = rng.randint(0, vocab, size=int(flip.sum()))
    return toks.astype(np.int32)


def image_pool(rows: int, image_shape, num_classes: int, seed: int,
               noise: float = 1.0):
    """Gaussian class-blob images in f32: y uniform over the classes,
    x = mu_y + noise.  Made class by class so that the means never exist as
    one ``[classes, pixels]`` array (600 MB at ImageNet shapes)."""
    rng = np.random.default_rng(seed)
    d = int(np.prod(image_shape))
    y = rng.integers(0, num_classes, size=rows).astype(np.int32)
    x = rng.standard_normal((rows, d), dtype=np.float32)
    if noise != 1.0:
        x *= np.float32(noise)
    for cls in np.unique(y):
        x[y == cls] += np.random.default_rng([seed, int(cls)]) \
            .standard_normal(d, dtype=np.float32)
    return x.reshape((rows, *image_shape)), y


def lm_batch(rows: np.ndarray) -> dict:
    """``{tokens, targets, positions}`` from raw rows ``[B, S + 1]``, the
    program's `models.transformer.lm_batch` contract."""
    b, s1 = rows.shape
    return {
        "tokens": rows[:, :-1].astype(np.int32),
        "targets": rows[:, 1:].astype(np.int32),
        "positions": np.broadcast_to(np.arange(s1 - 1, dtype=np.int32),
                                     (b, s1 - 1)).copy(),
    }


def make_pool(feed: dict, shapes: dict, seed: int) -> dict:
    """The host pool a cell draws from, by the `pool` kind of its feed."""
    kind = feed["pool"]
    if kind == "tokens":
        return {"rows": token_pool(feed["pool_rows"], shapes["seq_len"],
                                   shapes["vocab_size"], seed)}
    if kind == "images":
        x, y = image_pool(feed["pool_rows"], shapes["image_shape"],
                          shapes["num_classes"], seed)
        return {"x": x, "y": y}
    raise ValueError(f"unknown pool kind {kind!r}")


def to_batch(pool: dict, idx: np.ndarray) -> dict:
    if "rows" in pool:
        return lm_batch(pool["rows"][idx])
    return {k: v[idx] for k, v in pool.items()}


def pool_len(pool: dict) -> int:
    return len(next(iter(pool.values())))


def draw_stream(pool: dict, batch: int, seed: int) -> Iterator[dict]:
    """Host batches by seeded index draws with replacement, the way
    `train.py`'s LM loop feeds `opt.step` (which places the batch)."""
    rng = np.random.RandomState(seed)
    n = pool_len(pool)
    while True:
        yield to_batch(pool, rng.randint(0, n, size=batch))


def loader_stream(pool: dict, batch: int, seed: int, *, prefetch: int,
                  sharding) -> Iterator[dict]:
    """Device batches from the program's own `DataLoader` (native row
    gather, background prefetch, `device_put` onto the mesh), the way
    `train.py`'s classifier loop is fed.  The loader is the system under
    test here; only the pool and its parameters are the benchmark's."""
    from pytorch_ps_mpi_tpu.data.loader import DataLoader

    return iter(DataLoader(pool, batch_size=batch, seed=seed, epochs=None,
                           prefetch=prefetch, sharding=sharding))


def worker_batch_fn(pool: dict, batch: int, seed: int
                    ) -> Callable[[int, int], dict]:
    """``batch_fn(rank, it)`` for `AsyncPS.run`: each worker draws its own
    deterministic stream (copy of `async_ps.dataset_batch_fn`)."""
    n = pool_len(pool)

    def batch_fn(rank: int, it: int) -> dict:
        rng = np.random.default_rng(np.random.SeedSequence([seed, rank, it]))
        return to_batch(pool, rng.integers(0, n, size=batch))

    return batch_fn


def fixed_sample(pool: dict, n: int) -> dict:
    """The first ``n`` rows of the pool: the seeded sample the reference
    check runs on."""
    return to_batch(pool, np.arange(n))
