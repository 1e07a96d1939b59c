"""Read the collectives, the weights a program moves before it uses them,
the fusions that draw random bits an element, how often each Mosaic
kernel runs, which matmuls lie under control flow the device decides, and
which scope of the layer map every instruction belongs to, out of a
compiled program's text.

``compiled.as_text()`` is the program after the SPMD partitioner: what a
placement rule (runtime/zero.py) really costs is the collectives found
there, their result shapes, and how often the loop around them runs; what
a parameter tree's layout costs (models/mimo_v2.py) is the fusions and
copies that write a parameter again before a matmul reads it
(``parameter_rewrites``); what a random draw over an activation costs is
the threefry rounds fused into whatever reads the mask (``rng_fusions``);
what a remat policy saves or recomputes is how often a kernel runs a call
of the program (``kernel_calls``); whether a skip is a branch the device
takes or a ``select`` over work done anyway is where its matmuls lie
(``matmuls``); whose an instruction is, the attention's, the
experts', the head's or nobody's, is the ``jax.named_scope`` path in its
``op_name`` (``scopes``, ``scope_cycles``: the no-chip half of
telemetry/device_trace.py, which groups a capture's time the same way).
Bytes and counts only — no time is read from a program's text (a fusion's
``estimated_cycles`` is the compiler's guess, and is reported as that).
"""
from __future__ import annotations

import math
import re
from typing import Dict, List, NamedTuple

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
             "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s+=\s+(.*?)\s+([\w\-]+)\(")
_NAMED = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*?)\s+([\w\-]+)\(([^)]*)\)")
_OPERAND = re.compile(r"[\w.\-]+")
_ARRAY = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9]m[0-9](?:fn)?)?)\[([0-9,]*)\]"
                    r"(\{[^}]*\})?")
_CALLEE = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_COND = re.compile(r"\bcondition=%?([\w.\-]+)")
_CHANNEL = re.compile(r"\bchannel_id=(\d+)")
_CONST = re.compile(r"\bs32\[\][^ ]* constant\((\d+)\)")


class Collective(NamedTuple):
    op: str            # one of COLLECTIVES (a ``-start`` counts as its op)
    shapes: tuple      # result arrays: ((dtype, dims), ...)
    bytes: int         # of the results, one execution
    times: int         # executions a call of the program (loop trip counts)
    in_loop: bool      # inside some ``while`` body


def _laid(type_text: str):
    """((dtype, dims), layout text or '') of each array of a type."""
    return [((dt, tuple(int(d) for d in dims.split(",") if d)), layout)
            for dt, dims, layout in _ARRAY.findall(type_text)
            if dt in _ITEMSIZE]


def _arrays(type_text: str):
    return [array for array, _ in _laid(type_text)]


def _nbytes(arrays) -> int:
    return sum(_ITEMSIZE[dt] * math.prod(dims) for dt, dims in arrays)


def _computations(hlo_text: str):
    """(the lines of each computation by name, the entry's name)."""
    comps: Dict[str, list] = {}
    entry = current = None
    for line in hlo_text.splitlines():
        head = _HEADER.match(line)
        if head:
            current = head.group(2)
            comps[current] = []
            if head.group(1):
                entry = current
        elif current is not None and line.startswith("}"):
            current = None
        elif current is not None:
            comps[current].append(line)
    if entry is None:
        raise ValueError("no ENTRY computation in the program text")
    return comps, entry


def _reached(comps: Dict[str, list], entry: str):
    """(computation, line, result type, op, times, in_loop, at_run_time)
    of every instruction the entry computation reaches, ``times`` the
    product of the known trip counts of the loops around it (a loop whose
    count the compiler does not state counts once, and is still a loop);
    ``at_run_time``: whether it runs, or how often, is decided on the
    device: it lies in a branch of a ``conditional`` or in a loop of no
    stated count."""

    def trip_count(while_line: str):
        """The count the compiler states, else (the TPU's text states
        none) the one bound a counted loop's condition compares with,
        else None: the count is a value of the run."""
        stated = _TRIP.search(while_line)
        if stated:
            return int(stated.group(1))
        cond = _COND.search(while_line)
        bounds = {int(n) for line in comps.get(cond.group(1), ())
                  for n in _CONST.findall(line)} if cond else set()
        return bounds.pop() if len(bounds) == 1 else None

    def walk(name: str, times: int, in_loop: bool, at_run_time: bool,
             seen: tuple):
        if name not in comps or name in seen:
            return
        for line in comps[name]:
            m = _INSTR.match(line)
            if not m:
                continue
            type_text, op = m.groups()
            yield name, line, type_text, op, times, in_loop, at_run_time
            callees = _CALLEE.findall(line)
            for group in _BRANCHES.findall(line):
                callees += [c.strip().lstrip("%") for c in group.split(",")]
            loop = op == "while"
            n = trip_count(line) if loop else 1
            for callee in callees:
                yield from walk(
                    callee, times * (n or 1), in_loop or loop,
                    at_run_time or n is None or op == "conditional",
                    seen + (name,))

    return walk(entry, 1, False, False, ())


def collectives(hlo_text: str) -> List[Collective]:
    """Every collective the program runs, with the product of the known
    trip counts of the loops around it."""
    found: List[Collective] = []
    # the TPU's compiler splits an asynchronous collective into fusions
    # (start, steps, done) that each repeat the instruction under its one
    # channel: counted once
    channels = set()
    for _, line, type_text, op, times, in_loop, _ in _reached(
            *_computations(hlo_text)):
        base = op[:-len("-start")] if op.endswith("-start") else op
        channel = _CHANNEL.search(line)
        if base not in COLLECTIVES or (
                channel and (base, channel.group(1)) in channels):
            continue
        if channel:
            channels.add((base, channel.group(1)))
        arrays = _arrays(type_text)
        if op.endswith("-start"):
            # (operands..., results...): the results are the second half
            arrays = arrays[len(arrays) // 2:]
        found.append(Collective(base, tuple(arrays), _nbytes(arrays),
                                times, in_loop))
    return found


def collective_report(hlo_text: str) -> str:
    """A few lines for a log: bytes a call by operation, and the largest
    results inside loops."""
    found = collectives(hlo_text)
    lines = []
    for op in COLLECTIVES:
        mine = [c for c in found if c.op == op]
        if mine:
            lines.append(
                f"{op}: {len(mine)} instructions, "
                f"{sum(c.times for c in mine)} executions, "
                f"{sum(c.bytes * c.times for c in mine) / 1e9:.3f} GB a call")
    looped = sorted((c for c in found if c.in_loop),
                    key=lambda c: -c.bytes)[:12]
    for c in looped:
        shapes = ", ".join(f"{dt}[{','.join(map(str, dims))}]"
                           for dt, dims in c.shapes)
        lines.append(f"  in a loop x{c.times}: {c.op} {shapes} "
                     f"({c.bytes / 1e6:.2f} MB)")
    return "\n".join(lines)


class Rewrite(NamedTuple):
    instruction: str   # the fusion or copy, by its name in the entry
    op: str            # 'fusion' | 'copy'
    parameter: int     # the entry parameter it reads, by number
    bytes: int         # of its results
    hbm_bytes: int     # of those it leaves in HBM: the rest are in the
    #                    compiler's fast memory (``S(1)`` in the layout)


def parameter_rewrites(hlo_text: str, parameters: int,
                       share: float = 0.125) -> List[Rewrite]:
    """Every fusion or copy of the entry computation that reads one of
    the first ``parameters`` entry parameters (the leaves of a jitted
    function's first argument, its weights) and writes between ``share``
    of that parameter's bytes and all of them: the weight, or one layer
    of a stacked one, moved and not used (read directly or, a ``copy``,
    through a ``bitcast`` of the whole parameter: PR 55).  A matmul fused with its
    weight writes activations and is not listed while those are under
    the share or more than it read (a decode tick's are; a prefill's
    are as large as a matrix and do get listed); neither is an
    asynchronous ``copy-start`` or ``slice-start``, the compiler's own
    prefetch of an operand.  What a listed line costs a call: its bytes
    read once more, its ``hbm_bytes`` written and read again."""
    comps, entry = _computations(hlo_text)
    lines = [m.groups() for m in map(_NAMED.match, comps[entry]) if m]
    held, viewed = {}, {}
    for name, type_text, op, operands in lines:
        if op == "parameter" and int(operands) < parameters:
            held[name] = (int(operands), _nbytes(_arrays(type_text)))
        elif op == "bitcast":
            # the same bytes under another shape: ``copy(bitcast(param))``
            # is how the compiler writes a transpose of a whole weight (a
            # FUSION over such a view is a cache's update in place)
            source = held.get(operands.strip().lstrip("%"))
            if source is not None:
                viewed[name] = source
    found = []
    for name, type_text, op, operands in lines:
        if op not in ("fusion", "copy"):
            continue
        results = _laid(type_text)
        wrote = _nbytes(a for a, _ in results)
        in_hbm = _nbytes(a for a, layout in results if "S(1)" not in layout)
        for operand in _OPERAND.findall(operands):
            number, size = held.get(operand) or (
                op == "copy" and viewed.get(operand)) or (None, 0)
            if number is not None and share * size <= wrote <= size:
                found.append(Rewrite(name, op, number, wrote, in_hbm))
    return found


class RngFusion(NamedTuple):
    instruction: str   # the fusion, by its name where it is called
    elements: int      # of the largest u32 array the generator's body fills
    times: int         # executions a call of the program (loop trip counts)
    cycles: object     # the compiler's ``estimated_cycles`` of one
    #                    execution, None where the text states none


_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')


def rng_fusions(hlo_text: str, elements: int = 1 << 20,
                rounds: int = 20) -> List[RngFusion]:
    """Every fusion that draws random bits an element: its computation
    (with the fusions nested in it) holds ``rounds`` or more
    ``shift-right-logical`` and as many ``xor`` on u32 arrays of
    ``elements`` or more, which is threefry2x32's twenty rotations
    (``jax.random.bits`` / ``bernoulli`` / ``uniform`` over an activation)
    and not a counter hash's three shifts a site (``ops/dropout.py``).
    The key derivations of a step (``fold_in``, ``split``: threefry over a
    handful of words) are far under ``elements``."""
    comps, entry = _computations(hlo_text)
    # (where it is called, its line, times, the computation it calls)
    fusions = [(name, line, times, _CALLEE.search(line).group(1))
               for name, line, _, op, times, _, _ in _reached(comps, entry)
               if op == "fusion" and _CALLEE.search(line)]
    fused = {callee for *_, callee in fusions}

    def filled(computation: str) -> dict:
        """Sizes of the large u32 results of each of the two operations,
        through nested fusions."""
        sizes = {"shift-right-logical": [], "xor": []}
        for line in comps.get(computation, ()):
            m = _INSTR.match(line)
            if not m:
                continue
            if m.group(2) in sizes:
                sizes[m.group(2)] += [
                    math.prod(dims) for dt, dims in _arrays(m.group(1))
                    if dt == "u32" and math.prod(dims) >= elements]
            elif m.group(2) == "fusion" and _CALLEE.search(line):
                for op, inner in filled(
                        _CALLEE.search(line).group(1)).items():
                    sizes[op] += inner
        return sizes

    found = []
    for name, line, times, callee in fusions:
        if name in fused:
            continue    # nested: counted with the fusion that holds it
        sizes = filled(callee)
        if min(map(len, sizes.values())) >= rounds:
            cycles = _CYCLES.search(line)
            found.append(RngFusion(
                _NAMED.match(line).group(1),
                max(sizes["shift-right-logical"]), times,
                int(cycles.group(1)) if cycles else None))
    return found


_MOSAIC = 'custom_call_target="tpu_custom_call"'
_SUFFIX = re.compile(r"(\.\d+)+$")


def kernel_calls(hlo_text: str) -> Dict[str, int]:
    """Executions a call of the program of every Mosaic custom call of
    the chip's text (``tpu_custom_call``), by kernel: the
    instruction's name less its numeric suffix, which is the
    ``pl.pallas_call(name=...)`` (``ds_flash_fwd.10`` -> ``ds_flash_fwd``);
    each instruction counts the product of the trip counts of the loops
    around it.  A layer scan's forward loop holds a kernel once a layer,
    its backward loop once more where the block's remat runs it again."""
    found: Dict[str, int] = {}
    for _, line, _, op, times, _, _ in _reached(*_computations(hlo_text)):
        if op == "custom-call" and _MOSAIC in line:
            kernel = _SUFFIX.sub("", _NAMED.match(line).group(1))
            found[kernel] = found.get(kernel, 0) + times
    return found


class Matmul(NamedTuple):
    instruction: str   # the ``dot`` (the TPU's text: ``convolution``)
    shapes: tuple      # result arrays: ((dtype, dims), ...)
    at_run_time: bool  # in a branch of a ``conditional`` or in a loop
    #                    whose trip count is a value of the run


def matmuls(hlo_text: str) -> List[Matmul]:
    """Every matmul the program holds, fused or not, and whether the
    device decides at run time if (or how often) it runs: a
    ``lax.cond`` that stayed a branch and a ``while_loop`` of a traced
    trip count do; a ``cond`` that became a ``select`` under ``vmap``,
    and a ``scan``, do not (models/mlm_head.py walks the labelled rows
    in such a loop)."""
    return [Matmul(_NAMED.match(line).group(1), tuple(_arrays(type_text)),
                   at_run_time)
            for _, line, type_text, op, _, _, at_run_time in _reached(
                *_computations(hlo_text))
            if op in ("dot", "convolution")]


class Scoped(NamedTuple):
    instruction: str   # its name where it is called
    op: str            # opcode; a custom call adds ``:<its target>``
    scope: str         # scope path of its ``op_name``, '' where it has none
    op_name: str       # the ``op_name`` itself, '' where the metadata is empty
    cycles: object     # the compiler's ``estimated_cycles`` of one
    #                    execution, None where the text states none
    times: int         # executions a call of the program (loop trip counts)


UNSCOPED = "(unscoped)"
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_WRAPPED = re.compile(r"^([\w\-]+)\((.*)\)$")
_SCOPE = re.compile(r"^[A-Za-z_][\w.\-]*$")
# what JAX's own constructs leave in a name stack: the function a ``jit``
# names is dropped with it, the others are transparent
_CALLS = ("jit", "pjit", "xla_call")
_WRAPPERS = frozenset((
    "while", "body", "cond", "checkpoint", "remat", "rematted_computation",
    "closed_call", "core_call", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "custom_lin", "shard_map", "named_call",
    "pallas_call", "run_scoped"))
_BRANCH = re.compile(r"^branch_\d+(_fun)?$")


def _split(path: str) -> List[str]:
    """``a/jvp(b/c)/d`` -> [``a``, ``jvp(b/c)``, ``d``]."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(path[start:i])
            start = i + 1
    parts.append(path[start:])
    return parts


def _scope_parts(path: str) -> List[str]:
    parts: List[str] = []
    for part in _split(path):
        wrapped = _WRAPPED.match(part)
        if wrapped:
            if wrapped.group(1) not in _CALLS:
                parts += _scope_parts(wrapped.group(2))
        elif (part and part not in _WRAPPERS and not _BRANCH.match(part)
              and _SCOPE.match(part)):
            parts.append(part)
    return parts


def scope_path(op_name: str) -> str:
    """The ``jax.named_scope`` path of an ``op_name``: the path less its
    final primitive, less ``jit(...)`` with the function it names, with
    ``jvp(...)``, ``transpose(...)``, ``vmap(...)`` opened, and less what
    JAX's constructs add (``while`` / ``body`` / ``cond`` / ``branch_*``,
    ``checkpoint`` / ``remat``, ``closed_call``, ``custom_vjp_call``,
    ...), an ``einsum``'s subscripts and a scope a ``checkpoint`` repeats:
    ``jit(f)/transpose(jvp(layer))/while/body/checkpoint/attn/dot_general``
    -> ``layer/attn``; '' where nothing is left."""
    parts = _split(op_name)
    if not _WRAPPED.match(parts[-1]):
        parts = parts[:-1]      # the primitive (``jvp(embed)`` is a scope)
    kept: List[str] = []
    for part in _scope_parts("/".join(parts)):
        if not kept or kept[-1] != part:
            kept.append(part)
    return "/".join(kept)


def cut(scope: str, depth: int) -> str:
    """A scope path cut to ``depth`` names (each side of a ``mixed:``)."""
    if scope.startswith("mixed:"):
        sides = sorted({cut(s, depth) for s in scope[6:].split("+")})
        return sides[0] if len(sides) == 1 else "mixed:" + "+".join(sides)
    return "/".join(scope.split("/")[:depth]) or UNSCOPED


def scopes(hlo_text: str) -> List[Scoped]:
    """Every instruction the device runs as one operation (those of the
    entry and of the loop bodies, conditions and branches it reaches; what
    a fusion holds is part of the fusion), with the scope path of its
    ``op_name`` (``scope_path``).  A fusion whose own metadata is empty
    takes the scope its fused instructions share, ``mixed:<a>+<b>`` where
    they disagree (nested fusions opened)."""
    comps, entry = _computations(hlo_text)

    def named(line: str):
        m = _OP_NAME.search(line)
        return m.group(1) if m else None

    def fused(computation: str, seen: tuple = ()) -> set:
        found = set()
        for line in comps.get(computation, ()):
            m = _INSTR.match(line)
            if not m or m.group(2) in ("parameter", "constant"):
                continue
            op_name = named(line)
            callee = _CALLEE.search(line)
            if op_name is not None:
                found.add(scope_path(op_name))
            elif (m.group(2) == "fusion" and callee
                  and callee.group(1) not in seen):
                found |= fused(callee.group(1), seen + (computation,))
        return found

    # computations that are a fusion's (or a reduction's) inside: their
    # instructions are no operations of their own
    inner = set()
    for lines in comps.values():
        for line in lines:
            m = _INSTR.match(line)
            if m and m.group(2) not in ("while", "conditional", "call"):
                inner.update(_CALLEE.findall(line))
    found: List[Scoped] = []
    for comp, line, _, op, times, _, _ in _reached(comps, entry):
        if comp in inner or op in ("parameter", "constant", "tuple",
                                   "get-tuple-element", "bitcast"):
            continue
        op_name = named(line)
        scope = scope_path(op_name) if op_name is not None else ""
        if op_name is None and op == "fusion" and _CALLEE.search(line):
            shared = sorted(fused(_CALLEE.search(line).group(1)) - {""})
            scope = (shared[0] if len(shared) == 1
                     else "mixed:" + "+".join(shared) if shared else "")
        target = _TARGET.search(line) if op == "custom-call" else None
        cycles = _CYCLES.search(line)
        found.append(Scoped(
            _NAMED.match(line).group(1),
            op + (":" + target.group(1) if target else ""), scope,
            op_name or "", int(cycles.group(1)) if cycles else None, times))
    return found


_METADATA = re.compile(r',? ?metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')


def less_metadata(hlo_text: str) -> str:
    """The program's computations with every ``metadata={...}`` taken out
    (and without the tables of files, functions and stack frames ahead of
    them): what is left is what runs.  A ``jax.named_scope`` may change
    the metadata, never this."""
    comps, _ = _computations(hlo_text)
    return "\n".join(
        name + " {\n" + "\n".join(_METADATA.sub("", line) for line in lines)
        + "\n}" for name, lines in comps.items())


def scope_cycles(hlo_text: str, depth: int = 2) -> Dict[str, int]:
    """The compiler's ``estimated_cycles`` a call of the program (trip
    counts multiplied in) by scope cut to ``depth`` names, ``(unscoped)``
    for what no scope of the layer map owns: the compiler's guess, of the
    instructions it guesses for (fusions and copies; a Mosaic kernel, a
    ``while`` and a collective state none)."""
    total: Dict[str, int] = {}
    for s in scopes(hlo_text):
        if s.cycles:
            key = cut(s.scope, depth)
            total[key] = total.get(key, 0) + s.cycles * s.times
    return total
