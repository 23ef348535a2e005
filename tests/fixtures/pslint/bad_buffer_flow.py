"""pslint fixture — seeded buffer-ownership violations (PSL7xx).

The value-flow hazards of the zero-copy wire, one per rule: a caller's
buffer parked by reference (the stall-then-flush window), a buffer
mutated after hand-off, a zero-copy view escaping the scope that owns
its backing buffer, a recv buffer refilled under a live view, and a
donated jax buffer read after donation.  The clean twins
(``park_copy``, ``handoff_view``) prove materialization and the
``# pslint: transfers-ownership`` contract silence the rule; the
``allow()`` lines prove the escape hatch suppresses exactly what it
annotates.

Marker contract as in bad_lock.py.  Never imported — pslint only
parses (the ``jax`` names below are never resolved).
"""

from collections import deque

import jax


class ParkingLink:
    """The `Session._pending` shape: a send path that PARKS frames."""

    def __init__(self):
        self._pending = deque()
        self._net_queue = None
        self._sock = None

    def park_frame(self, payload):
        # Parks the CALLER's buffer by reference: the parked frame may
        # flush long after this returns, when the caller has legally
        # reused the buffer.
        self._pending.append(payload)  # [PSL701]

    def park_copy(self, payload):
        # Copy-on-park: bytes() severs the aliasing (free when the
        # frame is already immutable).
        self._pending.append(bytes(payload))

    def park_allowed(self, payload):
        self._pending.append(payload)  # pslint: allow(PSL701): demo  # [allowed:PSL701]

    def enqueue(self, frame_blob):
        # The queue form of the same hazard: a net-queue reference a
        # consumer thread drains later.
        self._net_queue.put(frame_blob)  # [PSL701]


def scatter_send(sock, leaf):
    """Mutation after hand-off: the kernel (or a parked reference) may
    not have consumed the buffer yet."""
    buf = bytearray(leaf)
    sock.sendall(buf)
    buf[0] = 0  # [PSL701]
    return buf


class SegmentedLink:
    """The v9 scatter-gather shapes: segment LISTS parked or iovec
    elements mutated after a ``sendmsg`` hand-off."""

    def __init__(self):
        self._pending = deque()

    def park_segments(self, segments):
        # Parks the caller's SEGMENT LIST by reference: every leaf view
        # in the iovec still aliases the caller's arrays when the
        # stalled frame finally flushes.
        self._pending.append(segments)  # [PSL701]

    def park_segments_copy(self, segments):
        # Copy-on-park, per segment — the clean twin (the real
        # `Session.send_data_segments` contract).
        parked = [bytes(s) for s in segments]
        self._pending.append(parked)


def gather_send(sock, leaf):
    """Mutating one element of an already-gather-sent iovec is the
    same hazard as mutating a sendall'd buffer — the iovec literal
    hands off EVERY element."""
    hdr = bytearray(8)
    buf = bytearray(leaf)
    sock.sendmsg([hdr, buf])
    buf[0] = 0  # [PSL701]
    return bytes(buf)


def leaf_view():
    """A zero-copy view of a scope-local buffer escaping unowned."""
    arena = bytearray(64)
    return memoryview(arena)  # [PSL702]


# The view deliberately carries the arena's ownership out (it is the
# sole reference) — the declared-contract twin of ``leaf_view``.
# pslint: transfers-ownership
def handoff_view():
    arena = bytearray(64)
    return memoryview(arena)


class DecodePlane:
    """Decode-side aliasing hazards."""

    def stash_view(self):
        arena = bytearray(128)
        self._last = memoryview(arena)  # [PSL702]

    def stash_allowed(self):
        arena = bytearray(32)
        self._keep = memoryview(arena)  # pslint: allow(buffer-ownership): demo  # [allowed:PSL702]

    def recv_loop(self, sock, n, out):
        # The preallocated-recv-buffer trap: refilling ``buf`` while a
        # zero-copy view of the previous payload escaped the iteration
        # makes every retained view silently re-read the NEXT frame.
        buf = bytearray(n)
        while True:
            sock.recv_into(buf)  # [PSL703]
            view = memoryview(buf)
            out.append(view)


def _apply(a, b):
    return a * b


def donated_reuse(x, y):
    """Read-after-donation through a literal-donating jit handle."""
    step = jax.jit(_apply, donate_argnums=(0,))
    out = step(x, y)
    return out + x  # [PSL704]


def donated_device_put(x, dev):
    y = jax.device_put(x, dev, donate=True)
    return y + x  # [PSL704]
