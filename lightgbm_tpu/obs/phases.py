"""Phases: names the device programs give their own XLA operations.

The Pallas kernels have names in a device trace and the host has seams;
what the engine compiles around the kernels had neither, and a reader
could only find a piece of it by where it lay in the trace. A PHASE is a
`jax.named_scope` the program enters while it is traced: it lands in the
`op_name` of every instruction of the compiled HLO and nowhere else, so
it changes no computation and costs a run nothing.

The trace itself does not carry it (an `XLA Ops` event is named by its
HLO text without metadata), but it names each event by the instruction's
name, and the compiled text has that name beside the `op_name`. So the
join is made by `table()`: every remembered program lowered and compiled
again (`compiled_text`: the run's own executable where it holds a kernel,
else a compile of its own), its text taken apart by `obs/hlo.py`, one row
an executed instruction. Nothing on the
training path calls `table()`; a run that never asks pays one tree-map
over a program's arguments at that program's first call (`remember`).

`PHASES` is closed, as `obs/events.py` is: `scope()` refuses a name that
is not here, so a reader's phase cannot drift from the program's.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Callable, Dict, List, Optional

from . import hlo

PREFIX = "gbdt."

# phase -> one line of what the program does there
PHASES: Dict[str, str] = {
    "build.head": "build program: the gradient lanes' write, the second "
                  "record buffer's zero fill, the empty tree tables",
    "build.park": "build program: the partition by the bag (park_pass) "
                  "and the copy back into the first buffer",
    "build.root": "build program: the root histogram (slot_hist_pass), "
                  "its expansion and its evaluation",
    "build.layout": "build program, every round: splits chosen, left "
                    "counts (the finder's, or count_pass where the "
                    "engine counts the rows), the new layout, move "
                    "destinations (move_pass), updated tables, per-chunk "
                    "counts, the round's counters",
    "build.eval": "build program, every round: child histograms and "
                  "split evaluation over the changed slots",
    "dp.psum": "build program under a mesh: the histograms of the root "
               "and of each round's children all-reduced over the chips",
    "build.replay": "build program: the leaf-wise replay on the device, "
                    "in a round and once behind the rounds",
    "build.copy_back": "build program: the rows out of the second buffer "
                       "after an odd number of rounds",
    "build.tail": "build program: cover values, committed chains, the "
                  "score-lane update, the parked rows' walk",
    "sample.goss": "GOSS's selection over the records: two counting "
                   "selects and the multiplier lane's write",
    "sample.bag": "plain bagging's draw over the records (one counting "
                  "select) or the host mask's write into the bag",
    "rank.scatter": "the score lane into the rank kernel's tile pack "
                    "(and the sort XLA puts ahead of a scatter)",
    "rank.glue": "the rank kernel's operand pack and result masks",
    "rank.gather": "gradients and hessians back by the index lane",
    "walk.tables": "a committed tree as the record walk's tables "
                   "(spec to compact tree, walk_expand)",
    "walk.apply": "the walk of trees over the training records: "
                  "walk_pass's operands",
    "valid.walk": "a validation set's walk: the tree's tables and "
                  "walk_pass over its packed records, or the XLA walk "
                  "over its row-order bins",
    "valid.metric": "metrics over a validation set's scores (and the "
                    "view of its packed score lane they read)",
    "train.metric": "metrics over the training scores",
    "drain.materialise": "a record lane back in row order",
    "setup.pack": "the record pack: a block of uint8 bins and the row "
                  "lanes into the records, on the device",
    "drain.undo": "the score-lane update of a round dispatched ahead of "
                  "its turn, taken back where a drain discards it",
}


def scope(name: str):
    """`jax.named_scope("gbdt." + name)` for a registered phase. Entered
    only while JAX traces; nested scopes are allowed and the innermost
    registered one is an instruction's phase."""
    if name not in PHASES:
        raise KeyError(f"phase {name!r} is not in obs.phases.PHASES")
    import jax
    return jax.named_scope(PREFIX + name)


def scoped(name: str):
    """Decorator: the whole of a traced function under `scope(name)`."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return run
    if name not in PHASES:
        raise KeyError(f"phase {name!r} is not in obs.phases.PHASES")
    return wrap


def phase_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost registered phase of an HLO `op_name`
    (`jit(f)/while/body/gbdt.build.eval/gather` -> `build.eval`)."""
    for part in reversed((op_name or "").split("/")):
        if part.startswith(PREFIX) and part[len(PREFIX):] in PHASES:
            return part[len(PREFIX):]
    return None


# ---- the programs a table is made of
_lock = threading.Lock()
_programs: Dict[Any, tuple] = {}    # (name, shapes) -> (fn, args, kwargs)
table_calls = 0                     # `table()` calls so far (test probe)


def _spec(x):
    """Shape and dtype of an array argument and nothing of its buffer;
    anything else (a Python scalar, a static value) as it is."""
    import jax
    if not (hasattr(x, "shape") and hasattr(x, "dtype")):
        return x
    sharding = getattr(x, "sharding", None)
    if sharding is not None and len(sharding.device_set) < 2:
        sharding = None     # one device: as an uncommitted operand lowers
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding,
                                weak_type=getattr(x, "weak_type", False))


def remember(name: str, fn: Callable, args=(), kwargs=None) -> None:
    """Keep what lowers `fn` again as it was just called: the jitted
    function and its arguments as `jax.ShapeDtypeStruct`s. One entry per
    (name, argument shapes); a later call of the same replaces it."""
    import jax
    if not hasattr(fn, "lower"):    # behind compile_cache's attribution
        fn = getattr(fn, "__wrapped__", fn)
    if not hasattr(fn, "lower"):
        return
    specs = jax.tree_util.tree_map(_spec, (tuple(args), dict(kwargs or {})))
    leaves, treedef = jax.tree_util.tree_flatten(specs)
    key = (name, treedef, tuple(
        (s.shape, str(s.dtype)) if hasattr(s, "shape") else repr(s)
        for s in leaves))
    with _lock:
        _programs[key] = (fn,) + specs


def remember_first(name: str, fn: Callable) -> Callable:
    """`fn` behind one `remember` of the arguments it is called with:
    for a program's first call, where it is jitted outside
    `AlignedEngine._program`."""
    def run(*args, **kwargs):
        remember(name, fn, args, kwargs)
        return fn(*args, **kwargs)
    return run


def programs() -> List[str]:
    with _lock:
        return [key[0] for key in _programs]


def forget() -> None:
    """Drop every remembered program (with `compile_cache.clear_programs`:
    a jitted function holds its closure)."""
    with _lock:
        _programs.clear()


# what tells the table's own compile of a program from the run's, in
# JAX's in-memory cache and in the persistent one: an option of another
# backend's code generator, at its default. It changes no code
_APART = {"xla_cpu_enable_fast_math": False}


@contextlib.contextmanager
def _metadata_in_cache_key():
    import jax
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag, None)
    if before is None:      # a jax without the flag: the cache as it is
        yield
        return
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, before)


def compiled_text(fn, args, kwargs) -> str:
    """The optimised HLO text of a remembered program, with the scopes of
    the source as it stands.

    JAX keys its persistent cache on a program's IR WITHOUT debug info, so
    a program that changed in its scopes alone is served the executable
    of before, whose text has the scopes of before (measured on the chip,
    PR 37: `goss_select` and `mat` came out of the cache the parent had
    filled, without a phase in them). A program that holds a Pallas kernel
    cannot be stale in that way: the kernel's payload carries its source
    lines, so the program compiles anew whenever they move, and the
    executable that ran (an in-memory hit here, no compile) has the
    scopes it was traced with. Every other program is compiled once more,
    apart from the run's executable and under a key that takes the
    metadata in: small programs, seconds the first time their source
    moved and a cache load after. The instruction names do not depend on
    metadata, so the fresh text names the events of the executable that
    ran."""
    lowered = fn.lower(*args, **kwargs)
    text = lowered.compile().as_text()
    if hlo.KERNEL_TARGET in text:
        return text
    with _metadata_in_cache_key():
        return lowered.compile(compiler_options=_APART).as_text()


def rows_of(program: str, text: str) -> List[Dict[str, Any]]:
    """The table's rows for one program's optimised HLO text: every
    instruction a trace can show (those inside a fusion or a reduction's
    `to_apply` never execute on their own) as `{program, instruction,
    opcode, shape, phase, source_file, source_line}`. An instruction's
    phase is, in this order: its own `op_name`'s; the one the instructions
    of the computation it `calls=` agree on (a fusion whose root has no
    name); the one its users agree on, else its operands (a copy or a
    piece of a cumsum the compiler made for them); that of the
    instruction whose body or branch it sits in (a copy the compiler put
    into a scoped loop); else None."""
    instrs = hlo.instructions(text)
    frame_table = hlo.frames(text)
    inside = hlo.inner_computations(instrs)
    by_comp: Dict[str, list] = {}
    for ins in instrs:
        by_comp.setdefault(ins.computation, []).append(ins)
    origin = {ins.name: hlo.origin(ins, frame_table) for ins in instrs}
    found = {ins.name: phase_of(origin[ins.name][0]) for ins in instrs}

    def agreed(names):
        seen = {found[n] for n in names if found.get(n) is not None}
        return seen.pop() if len(seen) == 1 else None

    def called(ins, depth=0):
        seen = set()
        for key, callee in hlo.callees(ins):
            for sub in by_comp.get(callee, ()) if key == "calls" else ():
                p = found[sub.name]
                if p is None and depth < 4:
                    p = called(sub, depth + 1)
                if p is not None:
                    seen.add(p)
        return seen.pop() if len(seen) == 1 else None

    shown = [ins for ins in instrs if ins.computation not in inside]
    for ins in shown:
        if found[ins.name] is None:
            found[ins.name] = called(ins)
    reads = {ins.name: hlo.operands(ins) for ins in shown}
    users: Dict[str, list] = {}
    for ins in shown:
        for name in reads[ins.name]:
            users.setdefault(name, []).append(ins.name)
    for _ in range(8):      # chains of unnamed pieces, from both ends
        changed = False
        for ins in shown:
            if found[ins.name] is None:
                p = agreed(users.get(ins.name, ())) \
                    or agreed(reads[ins.name])
                if p is not None:
                    found[ins.name], changed = p, True
        if not changed:
            break
    caller = {callee: ins for ins in shown
              for _, callee in hlo.callees(ins)}

    def phase(ins, depth=0):
        up = caller.get(ins.computation)
        if found[ins.name] is None and up is not None and depth < 16:
            return phase(up, depth + 1)
        return found[ins.name]

    out = []
    for ins in shown:
        if ins.opcode == "parameter":
            continue
        _, src, line = origin[ins.name]
        out.append({"program": program, "instruction": ins.name,
                    "opcode": ins.opcode,
                    "shape": hlo.plain_shape(ins.shape),
                    "phase": phase(ins), "source_file": src,
                    "source_line": line})
    return out


def table(only=None) -> List[Dict[str, Any]]:
    """One row per executed instruction of every remembered program (of
    those named in `only`, where given). Lowers and compiles each again:
    seconds, so only ever on request (after `jax.profiler.stop_trace()`,
    to name a profile's `XLA Ops` events by instruction name and result
    shape)."""
    global table_calls
    table_calls += 1
    with _lock:
        todo = [(key[0],) + val for key, val in _programs.items()
                if only is None or key[0] in only]
    out = []
    for name, fn, args, kwargs in todo:
        out += rows_of(name, compiled_text(fn, args, kwargs))
    return out
