"""Checker 7 — buffer-ownership dataflow (PSL7xx).

The zero-copy data plane ROADMAP item 1 commits to (scatter-gather
``sendmsg`` over raw per-leaf buffer views, preallocated recv buffers,
parked frames flushed long after the caller returned) lives or dies on
one invariant: **the bytes that hit the wire are the bytes the caller
computed**.  A buffer mutated after hand-off is silent numeric
corruption no CRC catches — the checksum is computed over the
already-wrong bytes — and Lian et al.'s convergence guarantee only
holds if the gradients applied are the gradients sent.  Li et al.'s
runtime enforces message immutability for them; ours does not, so the
linter does:

PSL701  ownership violated across a hand-off.  Two conviction forms:
        (a) a parking sink (``self._pending.append``, a queue ``put``)
        stores a CALLER-owned byte buffer (a byte-named function
        parameter — incl. the v9 wire's SEGMENT lists, which alias
        every caller-owned leaf view in the iovec) without ``bytes()``
        materialization in a function not annotated ``# pslint:
        transfers-ownership`` — the parked reference may flush long
        after the caller legally reused the buffer (the credit gate's
        stall-then-flush path makes this reachable today); (b) a
        buffer handed to a send/park sink — including every element of
        a ``sendmsg``/``send_frame_segments`` iovec literal — is
        MUTATED in place later in the same function — the retained
        reference (kernel, queue, parked frame) may not have consumed
        it yet.
PSL702  a zero-copy view (``memoryview``/``np.frombuffer``/
        ``np.ndarray(.., buffer, ..)``/ndarray ``.data``) of a
        function-LOCAL backing buffer ESCAPES the scope that owns the
        buffer (returned, stored on self, parked, yielded) without
        ``bytes()`` materialization — every later caller aliases
        memory whose ownership story ended with the frame.  Annotate
        ``# pslint: transfers-ownership`` when the view deliberately
        carries its backing buffer's ownership out (the serializer's
        encode arena: the view is the sole reference).
PSL703  decode-side aliasing: inside a loop, a recv/scratch buffer is
        REFILLED (``recv_into``/``readinto``/element assignment) while
        a zero-copy view of the previous iteration's payload escaped
        the iteration (appended, stored, yielded) — the retained view
        silently re-reads the NEXT frame's bytes.
PSL704  read-after-donation: a value handed to a donating jitted
        handle (constructed with a LITERAL ``donate_argnums``) or to
        ``jax.device_put(.., donate=True)`` is read again afterwards —
        the buffer was consumed; the read returns garbage or raises,
        depending on backend.

Scope and precision: the analysis is a per-function, statement-ordered
value-flow scan (nested ``def``/``lambda`` bodies are deferred work and
excluded), plus a per-loop aliasing pass for PSL703 and a corpus-wide
function table (`core.CorpusIndex.functions`) so calls into annotated
``transfers-ownership`` helpers classify as ownership transfers rather
than leaks.  Provenance heuristics are deliberately byte-shaped: parks
convict only byte-named parameters (``payload``/``blob``/``buf``/...),
mutation convicts only in-place operations.  What it cannot see —
interleavings, aliasing through containers, native pointers — is the
runtime sentinel's job (``PS_BUFFER_SENTINEL=1`` in ``transport.py``:
checksum at enqueue, re-verify at flush, typed `BufferMutatedError`).
"""

from __future__ import annotations

import ast
from collections import deque

from .core import (CorpusIndex, Finding, SourceModule, dotted_name,
                   fn_directives, is_self_attr)

RULE = "buffer-ownership"

# Parameter names that mark a caller-owned BYTE buffer (the park rule
# PSL701a convicts only these — a queue of decoded pytrees is not a
# byte hand-off).  "segment" covers the v9 scatter-gather iovec lists:
# a parked segment LIST aliases every caller-owned view in it, so
# parking it un-materialized is the same hazard as parking one buffer.
_BYTE_PARAM_HINTS = ("payload", "blob", "buf", "frame", "body", "msg",
                     "wire", "chunk", "data", "codes", "segment")
# Receivers whose .append/.appendleft/.put park a reference that may be
# consumed long after the caller returned (the transport's stall queue,
# net queues, thread inboxes).
_PARK_RECEIVERS = ("pending", "queue", "_q", "inbox", "jobs")
# Call names that hand a buffer to the wire/transport (the reference
# may be retained: parked frames, scatter-gather segments, kernel
# buffers under sendmsg).  The v9 segmented sinks hand WHOLE IOVECS:
# `sendmsg`/`sendmsg_all` gather-send a list of views, and
# `send_frame_segments`/`send_data_segments` are the frame- and
# session-level wrappers (the latter may PARK the list — copy-on-park
# is its contract).
_HANDOFF_CALLS = {"sendall", "sendmsg", "sendmsg_all", "send_frame",
                  "_send_frame", "send_frame_segments", "send_data",
                  "send_data_segments", "send", "_send",
                  "_send_control", "raw_send", "_push_grad",
                  # v10 READ-class sends (may park, copy-on-park).
                  "send_read"}
# Calls that produce a PRIVATE copy — materialization severs aliasing.
_MATERIALIZERS = {"bytes", "bytearray", "tobytes", "copy", "deepcopy",
                  "array", "asarray", "getvalue"}
# Calls that create a zero-copy VIEW of their buffer argument.
_VIEW_CALLS = {"memoryview", "frombuffer"}
# Calls that allocate a fresh (function-owned) mutable buffer.
_BUFFER_CREATORS = {"bytearray", "empty", "zeros", "ones", "empty_like",
                    "zeros_like", "ones_like"}
# Calls that REFILL/overwrite a buffer passed to them.
_REFILL_CALLS = {"recv_into", "readinto", "readinto1", "pack_into",
                 "copyto"}
# In-place methods that mutate a mutable byte buffer.
_MUTATING_METHODS = {"extend", "insert", "clear", "remove", "reverse"}


# -- value classification -----------------------------------------------------

class _Val:
    """Per-name provenance inside one function scope."""

    OWNED = "owned"          # fresh private buffer (creator/materializer)
    VIEW = "view"            # zero-copy view; .base names the backing var
    PARAM = "param"          # caller-owned (byte-named parameter, or alias)
    UNKNOWN = "unknown"

    __slots__ = ("kind", "base", "mutable")

    def __init__(self, kind: str, base: "str | None" = None,
                 mutable: bool = False):
        self.kind = kind
        self.base = base
        self.mutable = mutable


def _terminal(call: ast.Call) -> str:
    name = dotted_name(call.func)
    if name:
        return name.split(".")[-1]
    if isinstance(call.func, ast.Attribute):
        return call.func.attr
    return ""


def _base_name(expr: ast.AST) -> "str | None":
    """The variable a (possibly subscripted) buffer expression reads:
    ``buf`` / ``buf[a:b]`` -> 'buf'; attribute chains -> None (a
    pointer-ish ``x.ctypes.data`` is not a view of ``x``)."""
    while isinstance(expr, ast.Subscript):
        expr = expr.value
    if isinstance(expr, ast.Name):
        return expr.id
    return None


def _transfers_ownership(mod: SourceModule, fn) -> bool:
    return bool(fn_directives(mod, fn, "transfers-ownership"))


_VIEW_VOCAB = ("memoryview", "frombuffer", ".data", "ndarray")


def _view_vocab_in(mod: SourceModule, fn) -> bool:
    """Text-level pre-gate: a function whose source never mentions a
    view constructor cannot create one — skip its AST passes (string
    scan is ~100x cheaper than a body walk, and almost every function
    fails it)."""
    end = getattr(fn, "end_lineno", None) or fn.lineno
    seg = "\n".join(mod.lines[fn.lineno - 1:end])
    return any(tok in seg for tok in _VIEW_VOCAB)


def _fn_returns_view(mod: SourceModule, fn) -> bool:
    """True when ``fn``'s OWN returned expression creates a zero-copy
    view of one of its locals — the corpus-wide half of the value-flow:
    a caller of such a function receives an alias, not an owned buffer
    (unless the function is annotated ``transfers-ownership``, which
    makes the view CARRY the buffer's ownership out).  Nested defs are
    their own scope (`_own_walk`): a view-returning inner callback must
    not misclassify its factory."""
    if _transfers_ownership(mod, fn) or not _view_vocab_in(mod, fn):
        return False
    for node in _own_walk(fn):
        if isinstance(node, ast.Return) and node.value is not None:
            for expr in ast.walk(node.value):
                if _view_expr_base(expr) is not None:
                    return True
    return False


def _view_expr_base(expr: ast.AST) -> "str | None":
    """The backing variable when ``expr`` constructs a zero-copy view:
    ``memoryview(x)``, ``np.frombuffer(x, ..)``, ``np.ndarray(shape,
    dtype, x, ..)``, ``x[..].data``.  None otherwise."""
    if isinstance(expr, ast.Call):
        term = _terminal(expr)
        if term in _VIEW_CALLS and expr.args:
            return _base_name(expr.args[0])
        if term == "ndarray" and len(expr.args) >= 3:
            for arg in expr.args[2:]:
                base = _base_name(arg)
                if base is not None:
                    return base
    if (isinstance(expr, ast.Attribute) and expr.attr == "data"
            and isinstance(expr.value, (ast.Name, ast.Subscript))):
        # ndarray ``.data`` is a memoryview of the array; an attribute
        # receiver (``a.ctypes.data`` — a raw pointer int) is not.
        return _base_name(expr.value)
    return None


# -- per-function event scan --------------------------------------------------

class _Events:
    """Line-ordered value-flow events of one function body (nested
    defs/lambdas excluded — deferred work owns its own scope)."""

    def __init__(self):
        # (line, name, _Val) — name (re)bound
        self.binds: "list[tuple[int, str, _Val]]" = []
        # (line, name) — name handed to a send/park sink
        self.handoffs: "list[tuple[int, str]]" = []
        # (line, name, park-node) — caller-owned byte param parked
        self.param_parks: "list[tuple[int, str]]" = []
        # (line, name, how) — in-place mutation of name
        self.mutations: "list[tuple[int, str, str]]" = []
        # (line, name-or-None, base) — a view escaping the scope
        # (name None = a view expression escaping inline)
        self.escapes: "list[tuple[int, str | None, str]]" = []
        # (line, name) — plain reads (PSL704 use-after-donation)
        self.reads: "list[tuple[int, str]]" = []
        # (line, handle, [arg names consumed]) — donating-handle calls
        self.donations: "list[tuple[int, list[str]]]" = []


def _literal_donate_indices(call: ast.Call) -> "list[int] | None":
    """Positional indices of a LITERAL ``donate_argnums=``; None when
    the call does not donate literally."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return [v.value]
        if isinstance(v, (ast.Tuple, ast.List)):
            out = []
            for el in v.elts:
                if not (isinstance(el, ast.Constant)
                        and isinstance(el.value, int)):
                    return None
                out.append(el.value)
            return out
    return None


class _FnScan(ast.NodeVisitor):
    """Collect line-ordered events for one function body.  Branches are
    scanned in source order with one shared event stream — a deliberate
    over-approximation (a hand-off in one arm and a mutation in the
    other read as sequential); rebinding clears state, so the common
    ``v = fresh()`` loop idiom stays clean."""

    def __init__(self, mod: SourceModule, fn, events: _Events,
                 view_fns: "set[str]", owned_fns: "set[str]"):
        self.mod = mod
        self.fn = fn
        self.ev = events
        self.view_fns = view_fns
        self.owned_fns = owned_fns
        a = fn.args
        self.params = {p.arg for p in (*a.posonlyargs, *a.args,
                                       *a.kwonlyargs) if p.arg != "self"}
        self.byte_params = {p for p in self.params
                            if any(h in p.lower()
                                   for h in _BYTE_PARAM_HINTS)}
        # Donating handles bound in this scope: name -> indices.
        self.donating: "dict[str, list[int] | None]" = {}

    # Nested functions/lambdas are deferred work — their bodies run on
    # another timeline (thread targets, callbacks) and must not leak
    # events into this scope's ordering.
    def visit_FunctionDef(self, node):
        if node is not self.fn:
            return
        for stmt in node.body:
            self.visit(stmt)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        for d in (*node.args.defaults, *node.args.kw_defaults):
            if d is not None:
                self.visit(d)

    # -- classification helpers -------------------------------------------

    def _classify(self, expr: ast.AST) -> _Val:
        base = _view_expr_base(expr)
        if base is not None:
            return _Val(_Val.VIEW, base=base, mutable=True)
        if isinstance(expr, ast.Call):
            term = _terminal(expr)
            if term in _MATERIALIZERS:
                return _Val(_Val.OWNED, mutable=term == "bytearray")
            if term in _BUFFER_CREATORS:
                return _Val(_Val.OWNED, mutable=True)
            if term in self.view_fns:
                # A corpus function returning an unannotated view: the
                # leak is convicted in THAT function; the caller holds
                # an alias of foreign memory (not re-flagged here).
                return _Val(_Val.UNKNOWN)
            if term in self.owned_fns:
                return _Val(_Val.OWNED)
            return _Val(_Val.UNKNOWN)
        if isinstance(expr, ast.Name):
            if expr.id in self.byte_params:
                return _Val(_Val.PARAM)
            return _Val(_Val.UNKNOWN)
        if (isinstance(expr, ast.Constant)
                and isinstance(expr.value, bytes)):
            return _Val(_Val.OWNED)
        return _Val(_Val.UNKNOWN)

    # -- statement handlers -----------------------------------------------

    def visit_Assign(self, node):
        self.visit(node.value)
        val = self._classify(node.value)
        donate = (_literal_donate_indices(node.value)
                  if isinstance(node.value, ast.Call) else None)
        for t in node.targets:
            if isinstance(t, ast.Name):
                self.ev.binds.append((node.lineno, t.id, val))
                if donate is not None:
                    self.donating[t.id] = donate
            elif isinstance(t, ast.Subscript):
                base = _base_name(t)
                if base is not None:
                    self.ev.mutations.append(
                        (node.lineno, base, "element assignment"))
            elif is_self_attr(t):
                if donate is not None:
                    self.donating[t.attr] = donate
                self._escape_check(node.lineno, node.value,
                                   f"stored on self.{t.attr}")

    def visit_AugAssign(self, node):
        self.visit(node.value)
        if isinstance(node.target, ast.Subscript):
            base = _base_name(node.target)
            if base is not None:
                self.ev.mutations.append(
                    (node.lineno, base, "element update"))
        elif isinstance(node.target, ast.Name):
            # ``v += ...`` mutates in place only for mutable buffers;
            # the simulation decides using the bound provenance.
            self.ev.mutations.append(
                (node.lineno, node.target.id, "augmented assignment"))

    def visit_Return(self, node):
        if node.value is not None:
            self.visit(node.value)
            self._escape_check(node.lineno, node.value, "returned")

    def visit_Yield(self, node):
        if node.value is not None:
            self.visit(node.value)
            self._escape_check(node.lineno, node.value, "yielded")

    def _escape_check(self, line: int, expr: ast.AST, how: str) -> None:
        """Record every view construction (or view-valued name) inside
        an escaping expression."""
        for sub in ast.walk(expr):
            base = _view_expr_base(sub)
            if base is not None:
                self.ev.escapes.append((line, None, base))
        if isinstance(expr, ast.Name):
            self.ev.escapes.append((line, expr.id, ""))
        elif isinstance(expr, (ast.Tuple, ast.List)):
            for el in expr.elts:
                if isinstance(el, ast.Name):
                    self.ev.escapes.append((line, el.id, ""))

    def visit_Call(self, node):
        term = _terminal(node)
        recv = (node.func.value if isinstance(node.func, ast.Attribute)
                else None)
        recv_term = ""
        if recv is not None:
            recv_term = (dotted_name(recv) or (
                recv.attr if isinstance(recv, ast.Attribute) else "")
            ).split(".")[-1].lower()

        if term in ("append", "appendleft", "put", "put_nowait") and (
                any(h in recv_term for h in _PARK_RECEIVERS)):
            self._park(node)
        elif term in _HANDOFF_CALLS:
            # Iovec literals hand off every element: `sendmsg([hdr,
            # buf])` retains a kernel reference to ``buf`` exactly like
            # `sendall(buf)` would — explode list/tuple args (and
            # `[head, *segments]` splats) into per-name hand-offs.
            flat: "list[ast.AST]" = []
            for arg in node.args:
                if isinstance(arg, (ast.Tuple, ast.List)):
                    flat.extend(arg.elts)
                else:
                    flat.append(arg)
            for arg in flat:
                if isinstance(arg, ast.Starred):
                    arg = arg.value
                if isinstance(arg, ast.Name):
                    self.ev.handoffs.append((node.lineno, arg.id))
        elif term in _REFILL_CALLS:
            for arg in node.args:
                base = _base_name(arg)
                if base is not None:
                    self.ev.mutations.append(
                        (node.lineno, base, term))
        elif (term in _MUTATING_METHODS and isinstance(recv, ast.Name)):
            self.ev.mutations.append(
                (node.lineno, recv.id, f".{term}()"))
        elif term == "device_put":
            for kw in node.keywords:
                if (kw.arg == "donate"
                        and isinstance(kw.value, ast.Constant)
                        and kw.value.value is True and node.args
                        and isinstance(node.args[0], ast.Name)):
                    self.ev.donations.append(
                        (node.lineno, [node.args[0].id]))
        elif ((isinstance(node.func, ast.Name)
               and node.func.id in self.donating)
              or (is_self_attr(node.func)
                  and node.func.attr in self.donating)):
            idx = self.donating[node.func.id
                                if isinstance(node.func, ast.Name)
                                else node.func.attr]
            names = []
            for i, arg in enumerate(node.args):
                if idx is not None and i not in idx:
                    continue
                if isinstance(arg, ast.Name):
                    names.append(arg.id)
            if names:
                self.ev.donations.append((node.lineno, names))
        self.generic_visit(node)

    def _park(self, node: ast.Call) -> None:
        """A parking sink: record parked names (hand-off) and convict
        caller-owned byte params stored un-materialized (PSL701a —
        the simulation checks provenance at the park instant)."""
        values = list(node.args)
        exploded: "list[ast.AST]" = []
        for v in values:
            if isinstance(v, (ast.Tuple, ast.List)):
                exploded.extend(v.elts)
            else:
                exploded.append(v)
        for v in exploded:
            if isinstance(v, ast.Name):
                self.ev.handoffs.append((node.lineno, v.id))
                self.ev.param_parks.append((node.lineno, v.id))
                # A NAMED view parked is the same escape as the inline
                # form (`v = memoryview(arena); park(v)` == `park(
                # memoryview(arena))`) — provenance, not spelling.
                self.ev.escapes.append((node.lineno, v.id, ""))
            base = _view_expr_base(v)
            if base is not None:
                self.ev.escapes.append((node.lineno, None, base))

    def visit_Name(self, node):
        if isinstance(node.ctx, ast.Load):
            self.ev.reads.append((node.lineno, node.id))


# -- the per-function simulation ----------------------------------------------

def _merge_events(ev: _Events):
    """One line-ordered event stream: (line, order, kind, payload).
    Plain reads only matter to the donation rule (PSL704) — with no
    donation in the function they are dropped before the sort, which
    otherwise dominates the whole checker's cost (every Name load in
    the corpus)."""
    stream = []
    for line, name, val in ev.binds:
        stream.append((line, 0, "bind", (name, val)))
    for line, names in ev.donations:
        stream.append((line, 1, "donate", names))
    for line, name in ev.handoffs:
        stream.append((line, 1, "handoff", name))
    for line, name in ev.param_parks:
        stream.append((line, 1, "park", name))
    for line, name, base in ev.escapes:
        stream.append((line, 1, "escape", (name, base)))
    for line, name, how in ev.mutations:
        stream.append((line, 2, "mutate", (name, how)))
    if ev.donations:
        for line, name in ev.reads:
            stream.append((line, 3, "read", name))
    return sorted(stream, key=lambda e: (e[0], e[1]))


def _check_function(mod: SourceModule, fn, ctx: str, events: _Events,
                    findings: list) -> None:
    transfers = _transfers_ownership(mod, fn)
    a = fn.args
    params = {p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)
              if p.arg != "self"}
    byte_params = {p for p in params
                   if any(h in p.lower() for h in _BYTE_PARAM_HINTS)}
    # name -> _Val provenance; BYTE-named params seed as caller-owned
    # (and aliases of them inherit it — `parked = payload` is still the
    # caller's buffer); other params stay unknown, so a queue of
    # decoded pytrees never reads as a byte hand-off.
    vals: "dict[str, _Val]" = {
        p: _Val(_Val.PARAM if p in byte_params else _Val.UNKNOWN)
        for p in params}
    handed: "dict[str, int]" = {}      # name -> hand-off line
    donated: "dict[str, int]" = {}     # name -> donation line
    local_buffers: "set[str]" = set()  # names owning a local buffer

    for line, _order, kind, payload in _merge_events(events):
        if kind == "bind":
            name, val = payload
            vals[name] = val
            handed.pop(name, None)
            donated.pop(name, None)
            if val.kind == _Val.OWNED:
                local_buffers.add(name)
            else:
                local_buffers.discard(name)
        elif kind == "donate":
            for name in payload:
                donated.setdefault(name, line)
        elif kind == "handoff":
            handed.setdefault(payload, line)
        elif kind == "park":
            name = payload
            val = vals.get(name)
            # Provenance, not spelling: an ALIAS of a caller-owned byte
            # param (`parked = payload`) is exactly as parked-by-
            # reference as the param itself.
            if (not transfers
                    and val is not None and val.kind == _Val.PARAM):
                findings.append(Finding(
                    mod.path, line, "PSL701", RULE,
                    f"{ctx} parks caller-owned buffer {name!r} without "
                    f"materializing it — the parked reference may flush "
                    f"long after the caller legally reused the buffer "
                    f"(the stall-then-flush path), sending bytes the "
                    f"caller never computed",
                    hint=f"copy on park (`bytes({name})` — free for an "
                         f"already-immutable frame) or annotate the "
                         f"function `# pslint: transfers-ownership` and "
                         f"hold every caller to it"))
        elif kind == "escape":
            name, base = payload
            if name is None:
                # inline view expression escaping
                if base in local_buffers and not transfers:
                    findings.append(Finding(
                        mod.path, line, "PSL702", RULE,
                        f"{ctx} lets a zero-copy view of local buffer "
                        f"{base!r} escape the scope that owns it — "
                        f"every later reader aliases memory whose "
                        f"ownership story ended with this frame",
                        hint="materialize with bytes()/np.array() at "
                             "the boundary, or annotate "
                             "`# pslint: transfers-ownership` if the "
                             "view deliberately carries the buffer's "
                             "ownership out (sole reference)"))
                continue
            val = vals.get(name)
            if (val is not None and val.kind == _Val.VIEW
                    and val.base in local_buffers and not transfers):
                findings.append(Finding(
                    mod.path, line, "PSL702", RULE,
                    f"{ctx} lets view {name!r} (zero-copy over local "
                    f"buffer {val.base!r}) escape the owning scope "
                    f"un-materialized",
                    hint="materialize with bytes()/np.array() at the "
                         "boundary, or annotate "
                         "`# pslint: transfers-ownership` if the view "
                         "deliberately carries ownership out"))
        elif kind == "mutate":
            name, how = payload
            if how == "augmented assignment":
                val = vals.get(name)
                if val is None or not val.mutable:
                    # `v += b".."` on an immutable rebinds — treat as
                    # a bind that clears hand-off state.
                    handed.pop(name, None)
                    donated.pop(name, None)
                    continue
            if name in handed:
                findings.append(Finding(
                    mod.path, line, "PSL701", RULE,
                    f"{ctx} mutates buffer {name!r} ({how}) after "
                    f"handing it off at line {handed[name]} — a parked/"
                    f"queued/in-flight reference may still read it, so "
                    f"the bytes that flush are not the bytes that were "
                    f"handed off (and the CRC covers the wrong bytes)",
                    hint="hand off a private copy (bytes(...)), or "
                         "mutate a fresh buffer — never the one the "
                         "transport may still hold"))
                del handed[name]
        elif kind == "read":
            name = payload
            # The donating call's own argument read happens AT the
            # donation line — only a read strictly after it convicts.
            if name in donated and line > donated[name]:
                findings.append(Finding(
                    mod.path, line, "PSL704", RULE,
                    f"{ctx} reads {name!r} after it was donated at "
                    f"line {donated[name]} — the buffer was consumed "
                    f"by the donating call; this read returns garbage "
                    f"or raises depending on backend",
                    hint="use the donating call's RESULT, or drop "
                         "donation for values you still need (route "
                         "donate_argnums through the platform gate)"))
                del donated[name]


# -- PSL703: per-loop aliasing pass -------------------------------------------

def _own_walk(root: ast.AST):
    """``ast.walk`` (same breadth-first document order — the loop pass
    resolves view aliases in source order) that does NOT descend into
    nested function/lambda bodies: a nested def is its own scope
    (scanned by its own pass), and walking it from the enclosing
    function would double-report its loops with the wrong
    attribution."""
    todo = deque(ast.iter_child_nodes(root))
    while todo:
        node = todo.popleft()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        todo.extend(ast.iter_child_nodes(node))


def _check_loops(mod: SourceModule, fn, ctx: str, findings: list) -> None:
    """A loop that both REFILLS a buffer and lets a zero-copy view of it
    escape the iteration re-reads the next frame's bytes through the
    previous frame's view."""
    # Cheap text pre-gate first (no AST walk at all for the almost-
    # every function with no view vocabulary — what keeps the full-lint
    # wall-clock budget), then one structural pre-pass: without BOTH a
    # view construction and a loop in this scope the rule cannot fire.
    if not _view_vocab_in(mod, fn):
        return
    loops = []
    has_view = False
    for node in _own_walk(fn):
        if isinstance(node, (ast.While, ast.For)):
            loops.append(node)
        elif not has_view and _view_expr_base(node) is not None:
            has_view = True
    if not loops or not has_view:
        return
    for loop in loops:
        refills: "dict[str, int]" = {}
        live_views: "set[str]" = set()
        # view-name -> backing buffer, for views assigned in the loop
        view_of: "dict[str, str]" = {}
        for node in _own_walk(loop):
            if isinstance(node, ast.Call):
                term = _terminal(node)
                if term in _REFILL_CALLS:
                    for arg in node.args:
                        base = _base_name(arg)
                        if base is not None:
                            refills.setdefault(base, node.lineno)
                elif term in ("append", "appendleft", "add", "put",
                              "put_nowait"):
                    for arg in node.args:
                        base = None
                        if isinstance(arg, ast.Name):
                            base = view_of.get(arg.id)
                        if base is None:
                            base = _view_expr_base(arg)
                        if base is not None:
                            live_views.add(base)
            elif isinstance(node, ast.Assign):
                base = _view_expr_base(node.value)
                for t in node.targets:
                    if base is not None and isinstance(t, ast.Name):
                        view_of[t.id] = base
                    if isinstance(t, (ast.Attribute, ast.Subscript)):
                        vbase = _view_expr_base(node.value)
                        if vbase is None and isinstance(node.value,
                                                        ast.Name):
                            vbase = view_of.get(node.value.id)
                        if vbase is not None and not (
                                isinstance(t, ast.Subscript)
                                and _base_name(t) == vbase):
                            live_views.add(vbase)
                # Element assignment is also a refill of the target.
                for t in node.targets:
                    if isinstance(t, ast.Subscript):
                        tb = _base_name(t)
                        if tb is not None:
                            refills.setdefault(tb, node.lineno)
            elif isinstance(node, (ast.Yield, ast.YieldFrom)):
                val = getattr(node, "value", None)
                if val is not None:
                    base = _view_expr_base(val)
                    if base is None and isinstance(val, ast.Name):
                        base = view_of.get(val.id)
                    if base is not None:
                        live_views.add(base)
        for buf in sorted(live_views):
            if buf in refills:
                findings.append(Finding(
                    mod.path, refills[buf], "PSL703", RULE,
                    f"{ctx} refills recv buffer {buf!r} while a "
                    f"zero-copy view of the previous payload escaped "
                    f"the iteration — the retained view silently "
                    f"re-reads the NEXT frame's bytes",
                    hint=f"materialize the escaping payload "
                         f"(bytes(view)) before refilling {buf!r}, or "
                         f"rotate buffers so a live view never shares "
                         f"its backing store with the next receive"))


# -- entry point --------------------------------------------------------------

def _iter_functions(mod: SourceModule):
    """Every (fn, context-label) in the module: methods labelled
    ``Class.meth``, module functions by name.  Nested defs are reached
    through ast.walk but scanned as their OWN scope (the _FnScan of an
    outer fn skips them)."""
    for node in mod.nodes:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _fn_context(mod: SourceModule, fn,
                owners: "dict[int, str]") -> str:
    cls = owners.get(id(fn))
    return f"{cls}.{fn.name}" if cls else fn.name


def check(corpus: list[SourceModule],
          index: "CorpusIndex | None" = None) -> list[Finding]:
    findings: list[Finding] = []
    index = index or CorpusIndex(corpus)

    # Corpus-wide value-flow tables: functions returning unannotated
    # views (their callers hold aliases of foreign memory) vs functions
    # whose annotation transfers the backing buffer's ownership out
    # with the returned view (callers own what they got).
    view_fns: "set[str]" = set()
    owned_fns: "set[str]" = set()
    for fname, sites in index.functions.items():
        for mod, fn in sites:
            if _transfers_ownership(mod, fn):
                owned_fns.add(fname)
            elif _fn_returns_view(mod, fn):
                view_fns.add(fname)

    for mod in corpus:
        owners: "dict[int, str]" = {}
        for node in mod.nodes:
            if isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        owners[id(sub)] = node.name
        for fn in _iter_functions(mod):
            if fn.name == "__init__":
                continue  # construction: nothing external holds refs yet
            ctx = _fn_context(mod, fn, owners)
            events = _Events()
            scan = _FnScan(mod, fn, events, view_fns, owned_fns)
            scan.visit(fn)
            _check_function(mod, fn, ctx, events, findings)
            _check_loops(mod, fn, ctx, findings)
    return findings
