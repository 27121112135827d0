"""Every device microsecond of a profile under the scope it serves.

`scopes.py` names the program's parts through `jax.named_scope`, and a
reader of a profile gives each HLO op to the deepest name in its OWN
`op_name`. Ops the compiler makes after the scopes were written have no
name of their own, or only the name of the loop they sit in: the async
copies of memory-space assignment, fusions whose root XLA made, layout
passes. They do not exist when `named_scope` runs, so no scope in the
source can name them. The optimized HLO of the executable that ran says
what is INSIDE a nameless fusion and WHO CONSUMES a copy, and those have
names. `resolve` places each op of one module by the first rule that
applies and tags it with that rule:

- `own`: the deepest vocabulary name in the op's own `op_name` (whole
  path elements, the longer name on a tie, names inside
  `transpose(jvp(...))` found). Never moved by a later rule, but for an
  op of a compiler-made kind whose name is only a loop's ROOT (`collect`,
  `learn`, `replay`) because it inherited the `while`'s metadata (its
  `op_name` IS a `while`'s): that one goes on to `serves` and keeps its
  root only if `serves` finds nothing. The scan's own stacked write
  (`collect/while/body/dynamic_update_slice`) is the program's and stays.
- `inside`: a fusion (or `call` / `custom-call` / async wrapper) whose
  own name has no scope: the scope that holds most of the instructions
  of its fused computation, weighted by their output bytes; the deeper
  name on a tie.
- `serves`: the compiler-made kinds (`COMPILER_MADE`, and fusions that
  hold nothing else). A prefetch takes the resolved scope of its first
  consumer that is not itself such an op, followed through `tuple` /
  `get-tuple-element` and into a `while` body; an eviction (its value
  only reaches the computation's root tuple) takes its producer's; a
  `-done` goes where its `-start` goes.
- what no rule places stays None, with why. Nothing is guessed and
  nothing is apportioned: a fusion of two scopes' instructions is ONE op
  under one name, and the ledger only says so (`holds_other_scopes`).

`ledger` joins that to the per-op self times of a `jax.profiler`
profile. The HLO comes from the profile itself (the `.xplane.pb` carries
every module that ran; xprof hands its text back), so it is the
executable that RAN, not a fresh compile. Pure Python: no JAX here, and
xprof only inside `ledger` (scripts/obs_report.py --profile,
`ProfilerSession.close`; the benchmark's reducers in `run.py`'s parent).
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Iterable, NamedTuple

COMPILER_MADE = frozenset({
    "copy", "copy-start", "copy-done", "slice-start", "slice-done",
    "dynamic-slice", "dynamic-update-slice", "bitcast", "transpose",
    "reshape", "convert", "pad", "custom-call",
    "all-gather-start", "all-gather-done", "all-reduce-start",
    "all-reduce-done", "collective-permute-start", "collective-permute-done",
    "async-start", "async-update", "async-done", "send", "send-done",
    "recv", "recv-done"})
WRAPPERS = frozenset({"fusion", "call", "custom-call", "async-start"})
# an instruction that is no work of its own inside a fused computation
_FREE = frozenset({"parameter", "constant", "tuple", "get-tuple-element"})
_DTYPE_BYTES = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2,
                "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}
_ARRAY = re.compile(r"\b([a-z]+\d+[a-z0-9]*|pred)\[([\d,]*)\]")
_NAME = r"%?([\w.\-]+)"
_CALLS = re.compile(rf"\b(calls|body|condition|to_apply)={_NAME}")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")


class Instruction(NamedTuple):
    """One line of an HLO module's text."""

    name: str
    opcode: str
    op_name: str  # the `op_name` metadata, "" without
    operands: tuple[str, ...]
    calls: tuple[tuple[str, str], ...]  # (role, computation): calls, body, ...
    computation: str
    root: bool
    out_bytes: int
    index: int | None  # of a get-tuple-element or a parameter


def _balanced(text: str, start: int) -> int:
    """Index just past the parenthesis that closes the one at `start`."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _bytes_of(shape: str) -> int:
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n * _DTYPE_BYTES.get(dtype, 1)
    return total


def parse_hlo(text: str) -> list[Instruction]:
    """The instructions of an HLO module's text (`compiled.as_text()`, or
    what xprof's `graph_viewer` gives back from a profile), in the order
    written, which is the schedule of a compiled module."""
    out, computation = [], ""
    for line in text.splitlines():
        if not line.startswith(" "):
            m = re.match(rf"(?:ENTRY )?{_NAME} \(.*\{{\s*$", line)
            if m:
                computation = m[1]
            continue
        m = re.match(rf"\s+(ROOT )?{_NAME} = ", line)
        if not m:
            continue
        rest = line[m.end():]
        end = _balanced(rest, 0) if rest.startswith("(") else rest.find(" ")
        shape, rest = rest[:end], rest[end:].lstrip()
        paren = rest.find("(")
        if paren < 0:
            continue
        opcode, close = rest[:paren], _balanced(rest, paren)
        args, attrs = rest[paren + 1:close - 1], rest[close:]
        operands: tuple[str, ...] = ()
        index = None
        if opcode == "parameter":
            index = int(args) if args.strip().isdigit() else None
        elif opcode != "constant":
            operands = tuple(re.findall(r"%([\w.\-]+)", args)) or tuple(
                a.split()[-1] for a in args.split(",") if a.strip())
        if opcode == "get-tuple-element":
            found = re.search(r"\bindex=(\d+)", attrs)
            index = int(found[1]) if found else None
        calls = [(role, name) for role, name in _CALLS.findall(attrs)]
        for names in _CALL_LISTS.findall(attrs):
            calls += [("calls", n.strip().lstrip("%"))
                      for n in names.split(",") if n.strip()]
        meta = re.search(r'op_name="((?:[^"\\]|\\.)*)"', attrs)
        out.append(Instruction(m[2], opcode, meta[1] if meta else "", operands,
                               tuple(calls), computation, bool(m[1]),
                               _bytes_of(shape), index))
    return out


def scope_of(op_path: str, names: Iterable[str]) -> str | None:
    """The name that ends deepest in `op_path` as whole path elements
    (`/`-separated; a transformation wraps them in parentheses, as in
    `transpose(jvp(learn/loss))`); the longer name on a tie. The same
    answer as the benchmark's `scope_read.scope_of` (a test holds that)."""
    best, best_end = None, -1
    for name in names:
        for m in re.finditer(rf"(?:^|(?<=[/(])){re.escape(name)}(?=[/)]|$)",
                             op_path):
            if (m.end(), len(name)) > (best_end, len(best or "")):
                best, best_end = name, m.end()
    return best


def _under(scope: str, root: str) -> bool:
    return scope == root or scope.startswith(root + "/")


class _Module:
    """One module's instructions, indexed for the three rules."""

    def __init__(self, instructions: list[Instruction], vocabulary):
        self.names = sorted(vocabulary)
        self.by_name = {i.name: i for i in instructions}
        self.members: dict[str, list[Instruction]] = {}
        self.users: dict[str, list[str]] = {}
        self.caller: dict[str, tuple[str, Instruction]] = {}
        for inst in instructions:
            self.members.setdefault(inst.computation, []).append(inst)
            for op in dict.fromkeys(inst.operands):
                self.users.setdefault(op, []).append(inst.name)
            for role, comp in inst.calls:
                self.caller[comp] = (role, inst)
        # a compiler-made op that inherited a `while`'s metadata has its name
        self.loop_names = {i.op_name for i in instructions
                           if i.opcode == "while" and i.op_name}
        self._own: dict[str, str | None] = {}
        self._runs: dict[str, bool] = {}
        self._made: dict[str, bool] = {}
        self._placed: dict[str, tuple[str | None, str]] = {}
        self._held: dict[str, dict[str, int]] = {}

    # -- own ---------------------------------------------------------------
    def own(self, op_name: str) -> str | None:
        if op_name not in self._own:
            self._own[op_name] = scope_of(op_name, self.names)
        return self._own[op_name]

    def executed(self, inst: Instruction) -> bool:
        """Not an instruction inside a fused (or applied) computation."""
        comp = inst.computation
        if comp not in self._runs:
            role, caller = self.caller.get(comp, ("body", None))
            self._runs[comp] = caller is None or (
                (role in ("body", "condition")
                 or caller.opcode in ("call", "conditional"))
                and self.executed(caller))
        return self._runs[comp]

    # -- inside ------------------------------------------------------------
    def held(self, inst: Instruction) -> dict[str, int]:
        """Output bytes per scope of the instructions `inst` wraps."""
        if inst.name not in self._held:
            weights: dict[str, int] = {}
            self._held[inst.name] = weights  # a cycle adds nothing
            for _role, comp in inst.calls:
                for inner in self.members.get(comp, ()):
                    if inner.opcode in _FREE:
                        continue
                    if inner.calls:
                        for scope, w in self.held(inner).items():
                            weights[scope] = weights.get(scope, 0) + w
                    scope = self.own(inner.op_name)
                    if scope is not None:
                        weights[scope] = weights.get(scope, 0) + inner.out_bytes
        return self._held[inst.name]

    def _layout_only(self, inst: Instruction) -> bool:
        """A fusion that holds nothing but compiler-made kinds."""
        inner = [i for _r, comp in inst.calls for i in self.members.get(comp, ())
                 if i.opcode not in _FREE]
        return inst.opcode == "fusion" and bool(inner) and all(
            i.opcode in COMPILER_MADE and not i.calls for i in inner)

    def made(self, inst: Instruction) -> bool:
        if inst.name not in self._made:
            self._made[inst.name] = (
                inst.opcode in COMPILER_MADE and not inst.calls
                or self._layout_only(inst))
        return self._made[inst.name]

    def _passes(self, inst: Instruction) -> bool:
        """A compiler-made op with no scope of its own, or only the root
        it inherited with a loop's metadata: `serves` places it, and a
        consumer chain runs through it."""
        own = self.own(inst.op_name)
        return self.made(inst) and (own is None or (
            "/" not in own and inst.op_name in self.loop_names))

    # -- serves ------------------------------------------------------------
    def _forward(self, name: str, seen: set, carry: bool) -> str | None:
        """The scope of the first consumer of `name` that has one."""
        if name in seen:
            return None
        seen.add(name)
        for user_name in self.users.get(name, ()):
            user = self.by_name[user_name]
            if user.opcode == "tuple":
                scope = next(filter(None, (
                    self._forward_element(user, k, seen, carry)
                    for k, op in enumerate(user.operands) if op == name)), None)
            elif user.opcode == "get-tuple-element" or self._passes(user):
                scope = self._forward(user.name, seen, carry)
            else:
                scope = self.place(user.name)[0]
            if scope is not None:
                return scope
        return None

    def _elements(self, name: str, k: int) -> list[str]:
        return [u for u in self.users.get(name, ())
                if self.by_name[u].opcode == "get-tuple-element"
                and self.by_name[u].index == k]

    def _forward_element(self, tup: Instruction, k: int, seen: set,
                         carry: bool) -> str | None:
        """... of element `k` of the tuple `tup`: read back by a
        `get-tuple-element`, handed to a `while`, or (with `carry`) the
        root of a loop body, whose next pass and whose loop read it."""
        key = f"{tup.name}#{k}"
        if key in seen:
            return None
        seen.add(key)
        targets = self._elements(tup.name, k)
        loops = [self.by_name[u] for u in self.users.get(tup.name, ())
                 if self.by_name[u].opcode == "while"]
        role, caller = self.caller.get(tup.computation, ("", None))
        if tup.root and carry and role == "body":
            loops.append(caller)
        for loop in loops:
            body = dict(loop.calls).get("body")
            for param in self.members.get(body, ()):
                if param.opcode == "parameter":
                    targets += self._elements(param.name, k)
            targets += self._elements(loop.name, k)
        for target in targets:
            scope = self._forward(target, seen, carry)
            if scope is not None:
                return scope
        return None

    def _backward(self, name: str, seen: set) -> str | None:
        """The scope of the op that made the value `name` passes on."""
        if name in seen or name not in self.by_name:
            return None
        seen.add(name)
        inst = self.by_name[name]
        if inst.opcode == "get-tuple-element" and inst.operands:
            source = self.by_name.get(inst.operands[0])
            if source is None or source.opcode == "parameter":
                return None  # the chain ends in a parameter
            if source.opcode == "tuple" and inst.index < len(source.operands):
                return self._backward(source.operands[inst.index], seen)
            if source.opcode == "while":
                body = dict(source.calls).get("body")
                root = next((i for i in self.members.get(body, ()) if i.root),
                            None)
                if root and root.opcode == "tuple" \
                        and inst.index < len(root.operands):
                    return self._backward(root.operands[inst.index], seen)
                return None
            return self._backward(source.name, seen)
        if self._passes(inst):
            return self._backward(inst.operands[0], seen) \
                if inst.operands else None
        if inst.opcode == "parameter":
            return None
        return self.place(name)[0]

    def _serves(self, inst: Instruction) -> str | None:
        if inst.opcode.endswith("-done") and inst.operands:
            start = self.by_name.get(inst.operands[0])
            if start is not None and start.opcode.endswith(("-start", "-update")):
                return self._serves(start)
        return (self._forward(inst.name, set(), carry=False)
                or (self._backward(inst.operands[0], {inst.name})
                    if inst.operands else None)
                or self._forward(inst.name, set(), carry=True))

    # -- the rules in order ------------------------------------------------
    def place(self, name: str) -> tuple[str | None, str]:
        if name not in self._placed:
            self._placed[name] = (None, "a chain of copies that closes on itself")
            self._placed[name] = self._place(self.by_name[name])
        return self._placed[name]

    def _place(self, inst: Instruction) -> tuple[str | None, str]:
        own, made = self.own(inst.op_name), self.made(inst)
        if own is not None and not self._passes(inst):
            return own, "own"
        if own is None and inst.opcode in WRAPPERS and inst.calls:
            weights = self.held(inst)
            if weights:
                return max(weights, key=lambda s: (weights[s], s.count("/"),
                                                   len(s), s)), "inside"
        if made:
            scope = self._serves(inst)
            if scope is not None:
                return scope, "serves"
            if own is not None:
                return own, "own"
            return None, "a consumer chain that ends in a parameter"
        if inst.opcode in ("while", "conditional", "call"):
            return None, f"{inst.opcode} self time"
        if inst.calls:
            return None, "a fusion of nameless instructions"
        return None, "no name of the vocabulary"

    def others(self, inst: Instruction, scope: str | None) -> list[str]:
        """The scopes `inst` holds instructions of besides the one it is
        placed under and that one's parents (a scope holds its children,
        so a parent's instruction is at home there)."""
        if inst.opcode not in WRAPPERS:  # a loop's self time is not its body
            return []
        return [s for s in self.held(inst)
                if scope is None or not _under(scope, s)]


def resolve(instructions: list[Instruction],
            vocabulary: Iterable[str]) -> dict[str, tuple[str | None, str]]:
    """`{hlo_op_name: (scope | None, rule)}` for every instruction of ONE
    executable's optimized HLO that runs as an op of its own (not those
    inside a fused computation). `rule` is `own`, `inside` or `serves`;
    beside None it says why no rule placed the op."""
    module = _Module(instructions, vocabulary)
    return {i.name: module.place(i.name) for i in instructions
            if module.executed(i)}


def account(rows: list, modules: dict[str, list[Instruction]],
            vocabulary: Iterable[str]) -> dict:
    """The ledger of `rows` = [[program_id, hlo_op_name, op_name,
    self_us], ...] (a profile's `hlo_stats`) over `modules` =
    {program_id: instructions}. Seconds. `scopes` + `unresolved` add up
    to `total_s`; `own` is the view a reader of own names has (every op
    under the deepest name in its row's `op_name`, whatever rule placed
    it; the converter labels an op that has no metadata with the loop it
    sits in, so this view holds more than the HLO's own names);
    `holds_other_scopes[scope][other]` are the seconds of ops placed
    under `scope` that also hold instructions of `other`."""
    vocabulary = sorted(vocabulary)
    indexed = {pid: _Module(insts, vocabulary) for pid, insts in modules.items()}
    row_scope: dict[str, str | None] = {}
    scopes: dict[str, float] = {}
    own: dict[str, float] = {}
    by_rule: dict[str, dict[str, float]] = {"own": {}, "inside": {}, "serves": {}}
    holds: dict[str, dict[str, float]] = {}
    unresolved: dict[tuple[str, str], float] = {}
    total_us = 0.0
    for pid, hlo_name, op_name, self_us in rows:
        module = indexed.get(str(pid))
        if module is None or hlo_name not in module.by_name:
            # one module holds the name: the converter numbered it otherwise
            found = [m for m in indexed.values() if hlo_name in m.by_name]
            module = found[0] if len(found) == 1 else None
        total_us += self_us
        if op_name not in row_scope:
            row_scope[op_name] = scope_of(op_name, vocabulary)
        mine = row_scope[op_name]
        if mine is not None:
            own[mine] = own.get(mine, 0.0) + self_us
        scope, rule = (None, "not in the HLO the profile holds") \
            if module is None else module.place(hlo_name)
        if scope is None and mine is not None:
            # the row's name is the profile's own label (the converter
            # gives a nameless op the name of the loop it sits in): what
            # no rule places stays where a reader of own names has it
            scope, rule = mine, "own"
        if module is not None:
            for other in module.others(module.by_name[hlo_name], scope):
                into = holds.setdefault(scope, {})
                into[other] = into.get(other, 0.0) + self_us
        if scope is None:
            unresolved[hlo_name, rule] = unresolved.get((hlo_name, rule), 0.0) + self_us
        else:
            scopes[scope] = scopes.get(scope, 0.0) + self_us
            by_rule[rule][scope] = by_rule[rule].get(scope, 0.0) + self_us

    def seconds(table: dict) -> dict:
        return {k: v / 1e6 for k, v in sorted(table.items())}

    return {
        "total_s": total_us / 1e6,
        "scopes": seconds(scopes), "own": seconds(own),
        "by_rule": {rule: seconds(t) for rule, t in by_rule.items()},
        "holds_other_scopes": {s: seconds(t) for s, t in sorted(holds.items())},
        "unresolved": [[name, us / 1e6, why] for (name, why), us in
                       sorted(unresolved.items(), key=lambda kv: -kv[1])]}


def program_vocabulary() -> list[str]:
    """Every device scope `scopes.py` spells (its host spans and the
    cache tag left out): the vocabulary of the program's own reports."""
    from distributed_reinforcement_learning_tpu.observability import scopes

    return sorted({v for k, v in vars(scopes).items()
                   if k.isupper() and isinstance(v, str) and k != "CACHE_TAG"
                   and not v.startswith("anakin/")})


# -- the profile -----------------------------------------------------------
def xplane_path(profile_dir: str) -> str | None:
    """The newest `.xplane.pb` under `profile_dir` (the directory handed
    to `jax.profiler.start_trace`, or any directory below it)."""
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=lambda p: (os.path.dirname(p), p)) if paths else None


def read_profile(path: str) -> tuple[list, dict[str, str]]:
    """(`hlo_stats` rows [[program_id, hlo_op_name, op_name, self_us],
    ...], {program_id: the module's HLO text with metadata}) of one
    `.xplane.pb`, through xprof's converter (no JAX behind it). The
    converter leaves `<module>(<program_id>).hlo_proto.pb` files beside
    the profile; only the modules that have a row are turned into text."""
    from xprof.convert import raw_to_tool_data

    data, _ = raw_to_tool_data.xspace_to_tool_data([path], "hlo_stats", {})
    table = json.loads(data) if data else {}
    col = {c["id"]: i for i, c in enumerate(table.get("cols", []))}
    rows = [[str(row["c"][col["program_id"]]["v"]),
             row["c"][col["hlo_op_name"]]["v"],
             row["c"][col["tf_op_name"]]["v"],
             float(row["c"][col["total_self_time"]]["v"])]
            for row in table.get("rows", [])]
    raw_to_tool_data.xspace_to_tool_names([path])  # writes the hlo_proto files
    wanted, texts = {r[0] for r in rows}, {}
    for proto in glob.glob(os.path.join(glob.escape(os.path.dirname(path)),
                                        "*.hlo_proto.pb")):
        module = os.path.basename(proto)[:-len(".hlo_proto.pb")]
        found = re.search(r"\((\d+)\)$", module)
        if not found or found[1] not in wanted:
            continue
        text, _ = raw_to_tool_data.xspace_to_tool_data(
            [path], "graph_viewer", {"graph_viewer_options": {
                "type": "long_txt", "module_name": module, "show_metadata": 1}})
        texts[found[1]] = text.decode() if isinstance(text, bytes) else text
    return rows, texts


def ledger(profile_dir: str, vocabulary: Iterable[str]) -> dict | None:
    """`account` over the newest profile under `profile_dir`; None where
    there is none or no device op in it."""
    path = xplane_path(profile_dir)
    if path is None:
        return None
    rows, texts = read_profile(path)
    if not rows:
        return None
    return account(rows, {pid: parse_hlo(t) for pid, t in texts.items()},
                   vocabulary)


def write(led: dict, profile_dir: str) -> str:
    """`scope_ledger.json` beside the newest profile under `profile_dir`."""
    path = os.path.join(os.path.dirname(xplane_path(profile_dir)),
                        "scope_ledger.json")
    with open(path, "w") as f:
        json.dump(led, f, indent=1)
    return path


def host_spans(profile_dir: str) -> dict[str, list]:
    """{name: [count, total_seconds]} of the fused loops' `anakin/*`
    `chip_span`s on the host plane of the same profile (the device ops'
    own clock)."""
    from xprof.convert import raw_to_tool_data

    path = xplane_path(profile_dir)
    if path is None:
        return {}
    data, _ = raw_to_tool_data.xspace_to_tool_data([path], "trace_viewer", {})
    out: dict[str, list] = {}
    for e in json.loads(data)["traceEvents"] if data else ():
        if e.get("ph") == "X" and str(e.get("name", "")).startswith("anakin/"):
            entry = out.setdefault(e["name"], [0, 0.0])
            entry[0] += 1
            entry[1] += float(e["dur"]) / 1e6
    return out


def table(led: dict, top: int = 10) -> str:
    """The ledger as text: per scope the milliseconds, the share of the
    total self time, how much came by each rule, what the scope's ops
    hold of other scopes; then the largest unresolved ops."""
    whole = led["total_s"] or 1.0
    lines = [f"{'scope':36s} {'ms':>11s} {'share':>7s} {'own':>11s} "
             f"{'inside':>10s} {'serves':>10s}  holds of other scopes (ms)"]
    for scope, s in sorted(led["scopes"].items()):
        rules = [led["by_rule"][r].get(scope, 0.0) for r in ("own", "inside",
                                                             "serves")]
        held = ", ".join(f"{o} {1e3 * v:.2f}" for o, v in
                         led["holds_other_scopes"].get(scope, {}).items())
        lines.append(f"{scope:36s} {1e3 * s:11.3f} {100 * s / whole:6.2f}% "
                     f"{1e3 * rules[0]:11.3f} {1e3 * rules[1]:10.3f} "
                     f"{1e3 * rules[2]:10.3f}  {held}")
    left = sum(s for _n, s, _w in led["unresolved"])
    lines.append(f"{'(unresolved)':36s} {1e3 * left:11.3f} "
                 f"{100 * left / whole:6.2f}%")
    lines.append(f"{'(total self time)':36s} {1e3 * led['total_s']:11.3f}")
    for name, s, why in led["unresolved"][:top]:
        lines.append(f"  unresolved {name:40s} {1e3 * s:10.3f} ms  {why}")
    return "\n".join(lines)
