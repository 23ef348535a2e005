"""pslint fixture — seeded JIT-hygiene violations (PSL2xx).

Marker contract as in bad_lock.py.  Never imported — pslint only parses.
"""

import threading

import jax
import numpy as np


def build_pool():
    fns = []
    for i in range(4):
        fns.append(jax.jit(lambda x: x + i))  # [PSL201]
    warm = []
    for fn in (leaky, item_leak):
        warm.append(jax.jit(fn))  # pslint: allow(jit-hygiene): fixture demo  # [allowed:PSL201]
    return fns + warm


def leaky(params, batch):
    val = np.asarray(params)  # [PSL202]
    scale = float(batch)  # [PSL202]
    return val * scale


def item_leak(x):
    return x.item()  # [PSL202]


leaky_jit = jax.jit(leaky)
item_jit = jax.jit(item_leak)


class JitServer:
    def compile(self):
        self._fn = jax.jit(lambda x: x)

    def start(self):
        threading.Thread(target=self._on_conn, daemon=True).start()
        threading.Thread(target=self._lazy_conn, daemon=True).start()

    def _on_conn(self):
        return self._fn(1)  # [PSL203]

    def _lazy_conn(self):
        fn = jax.jit(lambda x: x)  # [PSL201]
        return fn(1)

    def serve(self):
        # Serve-loop invocation of a prewarmed handle is the sanctioned
        # pattern — no finding.
        return self._fn(2)
