"""Device time by the program's own named scopes, with no trace file kept.

A device event is named by its HLO instruction (`%fusion.168 = s32[5500000]{0}
fusion(...)`); which `jax.named_scope` that instruction was written under is
its `op_name` in the compiled module (`jit(f)/tree.L4/route/...`), and the
program hands that out for the very executables its dispatch seams keep:
`shifu_tpu.obs.profile.scope_table()`. `events(ctx)` joins the two by
instruction name; `by_scope(ctx)` sums the result. It is the grouping
`scripts/trace_by_scope.py` reads off a kept xplane file's `tf_op`, first
chip, inside the window.

An `op_name` is cut to the program's own scopes as that script cuts it: from
the first part that is one (`tree.`, `nn.` or `wdl.`, bare or wrapped by jax
under a `grad` as `jvp(...)` / `transpose(jvp(...))`), with one part more
where that is a tree phase (`tree.L4/route`, `tree.leaf/psum`) or a wrapper
(`nn.bwd/jvp(nn.fwd)`). One scope is read wherever it stands in the stack:
`tree.codes`. The whole-tree program only ever writes it inside a level's
`hist` (`tree.L1/hist/tree.codes/pad`: every level writes the code operand's
pad and the compiler keeps one), so a part `tree.codes` anywhere makes the
event `tree.codes`' and not that level's.

What is left over is in two remainders: UNSCOPED (the instruction is there
and carries no scope of the program's: what the compiler made itself, a
multi-output fusion, code outside every scope) and UNMATCHED (no kept
executable has an instruction of that name and result shape: a program
dispatched outside every seam). A name that several executables use is
settled by the event's result shape, and stays UNMATCHED where shapes cannot
settle it: never a guess.

With no trace, a program without `scope_table` (a parent commit) or one
that kept nothing, `events` is None and every reader built on it returns
None.
"""

from __future__ import annotations

import re
import sys
import time

UNSCOPED, UNMATCHED = "unscoped", "unmatched"

NAME = re.compile(r"%([\w.\-]+) = ")
# scripts/trace_by_scope.py's: a part of an op's name stack that is one of
# the program's own scopes, bare or as jax wraps it under a `grad`
SCOPE = re.compile(r"(?:^|\()(?:tree|nn|wdl)\.")
_WRAPPED = re.compile(r"(?:jvp|transpose)\(")
_PHASE = re.compile(r"(?:hist|psum|derive|scan|route)$")
CODES = "tree.codes"


def scope_of(op_name: str):
    """`jit(f)/tree.L4/route/dot_general` -> `tree.L4/route`,
    `jit(f)/tree.L1/hist/tree.codes/pad` -> `tree.codes`; None where no
    part is a scope of the program's."""
    parts = [p for p in op_name.split("/") if p] + [""]
    if CODES in parts:
        return CODES
    at = next((i for i, p in enumerate(parts) if SCOPE.search(p)), None)
    if at is None:
        return None
    first, second = parts[at], parts[at + 1]
    if _WRAPPED.match(second) or (first.startswith("tree.")
                                  and _PHASE.match(second)):
        return first + "/" + second
    return first


def bare(scope: str) -> str:
    """A scope's first part without jax's wrappers:
    `transpose(jvp(wdl.embed))` -> `wdl.embed`."""
    return re.sub(r"(?:jvp|transpose)\(|\)", "", scope.split("/")[0])


def attribute(op_seconds: dict, table: list) -> list:
    """[(event name, seconds, scope)] for `op_seconds` {event name:
    seconds} and `table` [(seam, {instruction: (shape, op_name)})]. An
    event's shape is read as the table's were (`profile.result_shape`)."""
    from shifu_tpu.obs.profile import result_shape

    out = []
    for event, s in op_seconds.items():
        m = NAME.match(event)
        hits = [ops[m.group(1)] for _seam, ops in table
                if m.group(1) in ops] if m else []
        found = set()
        if hits:
            shape = result_shape(event, m.end())
            found = {scope_of(op_name) or UNSCOPED
                     for sh, op_name in hits if sh == shape}
        out.append((event, s, found.pop() if len(found) == 1 else UNMATCHED))
    return out


def events(ctx):
    """`attribute` of the traced window's operations and the program's scope
    table, made once a run and kept on `ctx`; None where either is
    missing."""
    if "scope_events" not in ctx:
        from shifu_tpu.obs import profile

        tr, got = ctx.get("trace"), None
        if tr and hasattr(profile, "scope_table"):
            t0 = time.perf_counter()
            table = profile.scope_table()
            took = time.perf_counter() - t0
            if table:
                got = attribute(tr["op_seconds"], table)
                print("scope_table: %d executables, %d instructions in "
                      "%.3f s; join %.3f s" % (
                          len(table), sum(len(ops) for _, ops in table), took,
                          time.perf_counter() - t0 - took),
                      file=sys.stderr, flush=True)
        ctx["scope_events"] = got
    return ctx["scope_events"]


def by_scope(ctx):
    """{scope: seconds}, UNSCOPED and UNMATCHED among them; or None."""
    evs = events(ctx)
    if evs is None:
        return None
    out = {}
    for _event, s, scope in evs:
        out[scope] = out.get(scope, 0.0) + s
    return out


def ms_per(ctx, units: int, want):
    """Milliseconds a unit in the events `want(scope, event name)` takes;
    None with nothing to join or no unit in the window."""
    evs = events(ctx)
    if evs is None or not units:
        return None
    return 1e3 * sum(s for event, s, scope in evs
                     if want(scope, event)) / units


def trees(ctx) -> int:
    """`tree_xla_ms_per_tree`'s divisor."""
    return len(ctx["driver"].unit_ends)


def epochs(ctx) -> int:
    """`wdl_lookup_ms_per_epoch`'s divisor."""
    return len(ctx["calls"]) * getattr(ctx["driver"], "epochs", 0)


def tree_ms(ctx, scope_pattern: str):
    """Milliseconds a tree in the scopes `scope_pattern` matches whole, the
    tree kernel's own events left out (they are `tree_kernel_ms_per_tree`'s,
    found as `tree_xla_ms_per_tree` finds them)."""
    from benchmarks.lib import spec

    kernel = spec.load_module("layer_metrics",
                              "tree_kernel_ms_per_tree").KERNEL
    want = re.compile(scope_pattern)
    return ms_per(ctx, trees(ctx), lambda scope, event: bool(
        want.fullmatch(scope)) and not kernel.search(event))
