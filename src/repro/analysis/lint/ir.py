"""Post-optimization HLO text -> instruction graph (DESIGN.md §13.1).

The static-analysis rules (`analysis/lint/rules.py`) used to be regex
scans over raw HLO lines; they could see *shapes* but not *why* a buffer
exists or where it flows.  This module parses the compiled module into a
proper IR:

  * :class:`Instruction` — name, opcode, result shape(s), operand names,
    called computations, and the ``metadata={...}`` attributes
    (``op_name`` and the op's source file — the latter is how
    interpret-mode Pallas kernel bodies, which leak into CPU HLO as
    plain ops, are recognized and exempted from materialization rules).
    The source file comes from an inline ``source_file="..."`` or, as
    current XLA prints it, from ``stack_frame_id=N`` resolved through
    the module's ``FileNames`` / ``FileLocations`` / ``StackFrames``
    tables.
  * :class:`HloComputation` — ordered instructions + ROOT.
  * :class:`HloGraph` — all computations, global def-use edges
    (instruction names are module-unique), caller links, and the
    module-level ``input_output_alias`` donation table.

Def-use edges cross computation boundaries: a fusion/call/while
instruction links its operands to the called computation's parameters
positionally, and the called ROOT back to the call result (while bodies
additionally loop their ROOT back onto their carry parameter), so taint
propagation (`HloGraph.propagate`) follows values through fusions and
loops the way the runtime does.

The parser is deliberately tolerant: headerless fragments (tests feed
bare instruction lines) land in an implicit entry computation, and
unknown operand names are simply dangling (no edges).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, Iterable, List, Optional, Set, Tuple

DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# zero-size / opaque HLO types that legitimately carry no byte width
SIZELESS_DTYPES = ("token", "opaque", "tuple")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*"          # [ROOT] %name =
    r"(\(.*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?(?:[A-Z][0-9A-Z()]*)?)\s+"
    r"([\w\-]+)"                                 # opcode
    r"\(")
_COMP_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s+\([^)]*\)\s*->.*\{\s*$")
_CALLED_RE = re.compile(
    r"(?:to_apply|calls|body|condition|branch_computations)="
    r"(\{[^}]*\}|%[\w.\-]+)")
_NAME_RE = re.compile(r"%([\w.\-]+)")
_META_FILE_RE = re.compile(r'source_file="([^"]*)"')
_META_FRAME_RE = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW_RE = re.compile(r"^(\d+)\s+(.*)$")
_FILE_ID_RE = re.compile(r"file_name_id=(\d+)")
_LOCATION_ID_RE = re.compile(r"file_location_id=(\d+)")
_STACK_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")
_META_OP_RE = re.compile(r'op_name="([^"]*)"')
_KERNEL_PATH_RE = re.compile(r"kernels")
_ALIAS_PAIR_RE = re.compile(r"\(\s*(\d+)\s*,")


@dataclasses.dataclass(frozen=True)
class HloShape:
    """One array shape: primitive dtype + dims ('' dims == scalar)."""
    dtype: str
    dims: Tuple[int, ...]

    def nonunit(self) -> Tuple[int, ...]:
        return tuple(sorted(d for d in self.dims if d != 1))

    @property
    def byte_width(self) -> int:
        if self.dtype in DTYPE_BYTES:
            return DTYPE_BYTES[self.dtype]
        if self.dtype in SIZELESS_DTYPES:
            return 0
        raise ValueError(
            f"unknown HLO dtype {self.dtype!r} — add it to "
            "repro.analysis.lint.ir.DTYPE_BYTES so byte accounting "
            "cannot silently treat it as free")

    @property
    def size_bytes(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n * self.byte_width


def parse_shapes(type_text: str) -> Tuple[HloShape, ...]:
    """All array shapes in a result type (tuples yield every component)."""
    return tuple(HloShape(dt, tuple(int(x) for x in dims.split(",") if x))
                 for dt, dims in _SHAPE_RE.findall(type_text))


@dataclasses.dataclass
class Instruction:
    name: str                       # module-unique, no leading %
    opcode: str
    shapes: Tuple[HloShape, ...]    # >=1; tuples carry every component
    operands: Tuple[str, ...]       # operand instruction names
    computation: str
    line: str                       # raw source line (stripped)
    lineno: int                     # 1-based line in the HLO text
    is_root: bool = False
    called: Tuple[str, ...] = ()    # computations this instruction calls
    op_name: str = ""
    source_file: str = ""
    param_index: Optional[int] = None   # for opcode == 'parameter'

    @property
    def shape(self) -> HloShape:
        return self.shapes[0]

    @property
    def in_kernel(self) -> bool:
        """True when the op's source metadata points inside ``kernels/``
        — an interpret-mode Pallas kernel body leaked into the HLO.  On
        a real accelerator compile kernel internals live behind a
        custom-call and never produce such lines, so exempting them
        costs nothing there."""
        return bool(self.source_file
                    and _KERNEL_PATH_RE.search(self.source_file))


@dataclasses.dataclass
class HloComputation:
    name: str
    instructions: Dict[str, Instruction] = dataclasses.field(
        default_factory=dict)
    root: Optional[str] = None
    is_entry: bool = False

    @property
    def parameters(self) -> List[Instruction]:
        ps = [i for i in self.instructions.values()
              if i.opcode == "parameter"]
        ps.sort(key=lambda i: (i.param_index is None, i.param_index))
        return ps


class HloGraph:
    """Parsed module: computations + global def-use edges."""

    def __init__(self):
        self.computations: Dict[str, HloComputation] = {}
        self.instructions: Dict[str, Instruction] = {}
        self.entry: Optional[str] = None
        self.module_name: str = ""
        self.alias_pairs: int = 0     # input_output_alias entries (donation)
        self._users: Optional[Dict[str, List[str]]] = None
        self._xedges: Optional[Dict[str, List[str]]] = None

    # -- construction -------------------------------------------------------

    def _add(self, comp: HloComputation, instr: Instruction) -> None:
        comp.instructions[instr.name] = instr
        # duplicate names only happen in synthetic fragments; last wins
        self.instructions[instr.name] = instr
        if instr.is_root:
            comp.root = instr.name

    # -- queries ------------------------------------------------------------

    def __iter__(self) -> Iterable[Instruction]:
        return iter(self.instructions.values())

    def get(self, name: str) -> Optional[Instruction]:
        return self.instructions.get(name)

    def entry_parameters(self) -> List[Instruction]:
        if self.entry and self.entry in self.computations:
            return self.computations[self.entry].parameters
        return []

    def users(self, name: str) -> List[str]:
        if self._users is None:
            u: Dict[str, List[str]] = {}
            for instr in self.instructions.values():
                for op in instr.operands:
                    if op in self.instructions:
                        u.setdefault(op, []).append(instr.name)
            self._users = u
        return self._users.get(name, [])

    def _cross_edges(self) -> Dict[str, List[str]]:
        """Directed def->use edges across computation boundaries:
        call operand -> callee parameter, callee ROOT -> call result,
        and (while only) body ROOT -> body carry parameter."""
        if self._xedges is not None:
            return self._xedges
        x: Dict[str, List[str]] = {}

        def add(src: str, dst: str):
            x.setdefault(src, []).append(dst)

        for instr in self.instructions.values():
            for cname in instr.called:
                comp = self.computations.get(cname)
                if comp is None:
                    continue
                params = comp.parameters
                for j, p in enumerate(params):
                    if j < len(instr.operands):
                        add(instr.operands[j], p.name)
                    elif len(instr.operands) == 1:
                        # whiles/conditionals pass one carry tuple
                        add(instr.operands[0], p.name)
                if comp.root is not None:
                    add(comp.root, instr.name)
                    if instr.opcode == "while":
                        for p in params:
                            add(comp.root, p.name)
        self._xedges = x
        return x

    def propagate(self, seeds: Iterable[str],
                  stop: Optional[Callable[[Instruction], bool]] = None
                  ) -> Set[str]:
        """Forward value-taint: every instruction reachable from `seeds`
        along def-use edges (within computations, through fusion/call
        parameter links, around while loops).  Instructions for which
        `stop` is true are never tainted and never expanded — the logits
        rule stops at kernel-internal ops, so a tile buffer inside a
        Pallas body cannot taint anything outside it."""
        xe = self._cross_edges()
        tainted: Set[str] = set()
        work = [s for s in seeds if s in self.instructions]
        while work:
            n = work.pop()
            if n in tainted:
                continue
            instr = self.instructions[n]
            if stop is not None and stop(instr):
                continue
            tainted.add(n)
            work.extend(self.users(n))
            work.extend(xe.get(n, []))
        return tainted


def _balanced(text: str, start: int) -> int:
    """Index one past the ')' matching the '(' at `start`."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _parse_alias_pairs(header: str) -> int:
    """Number of output->input alias entries in the module header's
    ``input_output_alias={ {}: (0, {}, may-alias), ... }`` table —
    the compiled evidence that buffer donation actually took."""
    key = "input_output_alias={"
    at = header.find(key)
    if at < 0:
        return 0
    i = at + len(key) - 1
    depth = 0
    for j in range(i, len(header)):
        if header[j] == "{":
            depth += 1
        elif header[j] == "}":
            depth -= 1
            if depth == 0:
                body = header[i + 1:j]
                return len(_ALIAS_PAIR_RE.findall(body))
    return 0


def _frame_files(tables: Dict[str, Dict[int, str]]) -> Dict[int, str]:
    """stack frame id -> source file of the frame's own location."""
    out = {}
    for fid, frame in tables["StackFrames"].items():
        loc = _LOCATION_ID_RE.search(frame)
        loc_row = tables["FileLocations"].get(int(loc.group(1))) if loc \
            else None
        fname = _FILE_ID_RE.search(loc_row) if loc_row else None
        if fname:
            out[fid] = tables["FileNames"].get(int(fname.group(1)),
                                               "").strip('"')
    return out


def parse_hlo(hlo_text: str) -> HloGraph:
    """Parse post-optimization HLO text into an :class:`HloGraph`."""
    g = HloGraph()
    current: Optional[HloComputation] = None
    implicit: Optional[HloComputation] = None
    tables: Dict[str, Dict[int, str]] = {t: {} for t in _STACK_TABLES}
    table: Optional[str] = None
    frames: Dict[str, int] = {}         # instruction -> stack frame id

    for lineno, raw in enumerate(hlo_text.splitlines(), start=1):
        line = raw.rstrip()
        stripped = line.strip()
        if not stripped:
            table = None
            continue
        if stripped.startswith("HloModule"):
            g.module_name = stripped.split(",", 1)[0].split()[-1]
            g.alias_pairs = max(g.alias_pairs, _parse_alias_pairs(stripped))
            continue
        if stripped in _STACK_TABLES:
            table = stripped
            continue
        if table is not None:
            row = _TABLE_ROW_RE.match(stripped)
            if row is not None:
                tables[table][int(row.group(1))] = row.group(2)
                continue
            table = None
        m = _INSTR_RE.match(line)
        if m is None:
            cm = _COMP_RE.match(line)
            if cm is not None:
                comp = HloComputation(cm.group(2),
                                      is_entry=bool(cm.group(1)))
                g.computations[comp.name] = comp
                if comp.is_entry:
                    g.entry = comp.name
                current = comp
            elif stripped == "}":
                current = None
            continue

        is_root, name, type_text, opcode = (bool(m.group(1)), m.group(2),
                                            m.group(3), m.group(4))
        shapes = parse_shapes(type_text)
        if not shapes:
            shapes = (HloShape(type_text.strip("(){} "), ()),)
        # operand list: balanced parens right after the opcode
        paren_at = m.end() - 1
        paren_end = _balanced(line, paren_at)
        arg_text = line[paren_at + 1:paren_end - 1]
        attrs = line[paren_end:]
        operands = tuple(_NAME_RE.findall(arg_text))
        param_index = None
        if opcode == "parameter":
            operands = ()
            try:
                param_index = int(arg_text.strip())
            except ValueError:
                pass
        called: List[str] = []
        for cm2 in _CALLED_RE.finditer(attrs):
            called.extend(_NAME_RE.findall(cm2.group(1)))
        fm = _META_FILE_RE.search(attrs)
        om = _META_OP_RE.search(attrs)
        sm = _META_FRAME_RE.search(attrs)
        if fm is None and sm is not None:
            frames[name] = int(sm.group(1))

        if current is None:
            if implicit is None:
                implicit = HloComputation("<implicit>", is_entry=True)
                g.computations[implicit.name] = implicit
                if g.entry is None:
                    g.entry = implicit.name
            target = implicit
        else:
            target = current
        g._add(target, Instruction(
            name=name, opcode=opcode, shapes=shapes, operands=operands,
            computation=target.name, line=stripped, lineno=lineno,
            is_root=is_root, called=tuple(called),
            op_name=om.group(1) if om else "",
            source_file=fm.group(1) if fm else "",
            param_index=param_index))
    if frames:
        files = _frame_files(tables)
        for name, fid in frames.items():
            g.instructions[name].source_file = files.get(fid, "")
    return g
