"""The MoE step's stages in a device trace.

The program wraps each stage of the MoE step and of the NIMBLE dataplane in
``jax.named_scope("nimble.<stage>")``: ``route``, ``dispatch``, ``pack``,
``plan``, ``rounds``, ``reassemble``, ``ffn`` and ``combine``.  Each op of the
compiled step carries its scope path in its ``op_name`` metadata, through
``shard_map``, ``scan``, ``checkpoint`` and the VJP
(``transpose(jvp(nimble.ffn))``).  A TPU trace names a device op by its HLO
instruction only, so the compiled step's HLO text maps instruction names to
scopes.  An op belongs to the innermost ``nimble.*`` component of its path,
so that the stages partition the step: ``nimble.combine/nimble.rounds`` is
``nimble.rounds``; an op outside every scope is unscoped (``""``).

A per-layer reader sees only the harness's ``Reading``, which holds the
trace's ops but not the step they ran.  :func:`scoped_ops` therefore finds the
runner in the frame of the harness's ``traced`` call and compiles its step
again (a compile-cache load, after the window).  Anything missing, on any
program without the scopes too, reads as nothing: no reader raises.
"""

from __future__ import annotations

import dataclasses
import itertools
import re
import sys
import traceback

from bench import trace as tr

SCOPE = re.compile(r"\bnimble\.([a-z_]+)")
COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
INSTRUCTION = re.compile(r"^\s+(ROOT )?%([\w.\-]+) = (.*)$")
REFERENCE = re.compile(r"%([\w.\-]+)")
OP_NAME = re.compile(r'\bop_name="([^"]*)"')
UNSCOPED = "unscoped"


@dataclasses.dataclass(frozen=True)
class ScopedOp(tr.Op):
    scope: str = ""     # innermost ``nimble.*`` scope, or "" outside them


def scope_of(op_name: str) -> str:
    """The innermost ``nimble.*`` component of an op_name path, or ""."""
    found = SCOPE.findall(op_name)
    return f"nimble.{found[-1]}" if found else ""


def instructions(hlo_text: str):
    """(computation, instruction, op_name or None, names it references, is
    root) for every instruction of HLO text, in the text's order."""
    computation = None
    for line in hlo_text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = INSTRUCTION.match(line)
        if m and computation is not None:
            root, name, rest = m.groups()
            meta = OP_NAME.search(rest)
            yield (computation, name, meta.group(1) if meta else None,
                   REFERENCE.findall(rest), bool(root))
        elif line == "}":
            computation = None


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> innermost scope, from compiled HLO text.

    An instruction takes the scope of its own ``op_name``.  The compiler
    leaves some instructions it makes without one (a decomposed all-gather,
    a fusion of slices after a ``collective-permute-done``, an async copy);
    such an instruction takes the scope of the computation it calls (its
    root's, else the commonest among its instructions), else that of its
    first user that has one, else that of its first operand that has one.
    """
    scopes: dict[str, str] = {}
    of_computation: dict[str, str] = {}
    for computation, body in itertools.groupby(instructions(hlo_text),
                                               key=lambda i: i[0]):
        body = [(name, None if op_name is None else scope_of(op_name), refs,
                 root) for _, name, op_name, refs, root in body]
        of_computation[computation] = _resolve(body, of_computation, scopes)
    return scopes


def _resolve(body: list, of_computation: dict, scopes: dict) -> str:
    """Scopes of one computation's instructions, written into ``scopes``;
    returns the computation's own scope."""
    own = {}
    for name, scope, refs, _ in body:
        if scope is None:
            called = [of_computation[r] for r in refs if r in of_computation]
            scope = next((c for c in called if c), None)
        own[name] = scope
    users: dict[str, list] = {}
    for name, _, refs, _ in body:
        for r in refs:
            users.setdefault(r, []).append(name)
    for name, _, _, _ in reversed(body):
        if own[name] is None:
            own[name] = next((own[u] for u in users.get(name, [])
                              if own[u]), None)
    for name, _, refs, _ in body:
        if own[name] is None:
            own[name] = next((own[r] for r in refs if own.get(r)), "")
    scopes.update(own)
    root = next((own[n] for n, _, _, is_root in body if is_root), "")
    if root:
        return root
    found = [s for s in own.values() if s]
    return max(set(found), key=found.count) if found else ""


def tag(ops: list, scopes: dict[str, str]) -> list[ScopedOp]:
    """The ops with their scopes; an op the HLO does not name is unscoped."""
    return [ScopedOp(o.name, o.start, o.dur, o.category,
                     scopes.get(o.name, "")) for o in ops]


def scope_s(ops: list[ScopedOp], scope: str, t0: float, t1: float) -> float:
    """Union of the intervals of the scope's leaf ops inside [t0, t1]; a
    control-flow op that spans its body is not counted."""
    return tr.busy_s([o for o in tr.leaves(ops) if o.scope == scope], t0, t1)


def top_scopes(ops: list[ScopedOp], t0: float, t1: float) -> list[list]:
    """[[scope, s]] for every scope, most time first (ties by name),
    ``unscoped`` last."""
    found = {o.scope for o in tr.leaves(ops)}
    rows = sorted(([s, scope_s(ops, s, t0, t1)] for s in found if s),
                  key=lambda row: (-row[1], row[0]))
    if "" in found:
        rows.append([UNSCOPED, scope_s(ops, "", t0, t1)])
    return rows


def step_hlo(runner) -> str:
    """The compiled HLO text of a ``moe_fwd`` runner's step."""
    return runner.fn.lower(runner.params, runner.xs[0]).compile().as_text()


def _traced_runner():
    frame = sys._getframe(1)
    while frame is not None:
        if frame.f_code.co_name == "traced" and "runner" in frame.f_locals:
            return frame.f_locals["runner"]
        frame = frame.f_back
    return None


_memo: list = [None, None]      # [reading, its scoped ops]


def scoped_ops(r) -> list[ScopedOp] | None:
    """The reading's ops tagged with their scopes, or None where the trace
    has no device ops, the step cannot be found, or no op has a scope.
    Built once per reading; the breakdown goes to the log."""
    if _memo[0] is r:
        return _memo[1]
    from bench import harness

    ops = None
    runner = _traced_runner() if r.ops else None
    if r.ops and runner is None:
        harness.log("WARNING: the scope metrics read nothing: no runner in "
                    "the frame of a call named 'traced' (bench/scopes.py "
                    "looks it up by that name until the harness hands the "
                    "readers the step's HLO)")
    if runner is not None:
        try:
            scopes = hlo_scopes(step_hlo(runner))
        except Exception:        # a reader must not end the run
            harness.log(f"scopes: no step HLO to read\n"
                        f"{traceback.format_exc()}")
        else:
            ops = tag(r.ops, scopes)
            rows = top_scopes(ops, r.t0, r.t1)
            busy = tr.busy_s(r.ops, r.t0, r.t1)
            unnamed = sum(o.name not in scopes for o in tr.leaves(r.ops))
            harness.log(f"scopes: {rows}; busy_s {busy}; leaf ops the HLO "
                        f"does not name: {unnamed}")
            if all(o.scope == "" for o in ops):
                ops = None
    _memo[:] = [r, ops]
    return ops


def per_call_ms(r, scope: str):
    """Device milliseconds per call in ``scope`` on the pace-setting chip, or
    None where the scope does not appear."""
    ops = scoped_ops(r)
    if ops is None or not any(o.scope == scope for o in ops):
        return None
    return r.per_call_ms(scope_s(ops, scope, r.t0, r.t1))
