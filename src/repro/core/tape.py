"""Plan -> tape compiler: a static, device-executable form of a plan.

A :class:`PlanTape` is the straight-line program a plan's executor *would*
perform, recorded ahead of time.  This is possible because every executor in
this codebase (BestDMachine, ShallowFish's Algorithm 4, NoOrOpt's recursion)
has **data-independent control flow**: which set operations run depends only
on the tree structure and the atom ordering, never on the contents of the
record sets.  Tracing an execution with an op-recording ``SetBackend``
therefore yields a program that is valid for *any* table — and that a device
backend (``columnar.device.DeviceTapeBackend``) can run as one compiled
device program with zero per-step host round-trips.

Tape ops (SSA over bitmap "slots"):

``FULL / EMPTY``  materialize the constant full / empty record set
``ATOM``          dst = src ∧ P(atom)        (one costed column touch)
``CHAIN``         dst = src ∧ (∧/∨ of K sibling atoms) — lowers to the
                  fused multi-column kernel ``kernels.fused_chain.
                  fused_chain_scan`` (one pass over src's blocks for all K)
``SETOP``         dst = a {∩, ∪, \\} b       (``kernels.bitmap_ops`` opcodes)

Compilation pipeline:

1. **Trace** — drive a :class:`~repro.core.bestd.BestDMachine` (or NoOrOpt's
   executor) over the plan order with an emitter backend; every backend call
   appends an op and returns a fresh virtual slot.
2. **Chain fusion** — maximal runs of sibling atoms that (a) are *all* the
   children of one inner node, (b) are all device-evaluable comparisons, and
   (c) appear consecutively in the order, are emitted as a single CHAIN op
   and absorbed into the machine via
   :meth:`~repro.core.bestd.BestDMachine.absorb_chain`.  A CHAIN lists its
   atoms in (column, op) order, not the plan's, so chains that differ only
   in order share one key.  Fusing only whole
   leaf groups is what makes this safe: no lineage outside the group ever
   references an individual fused atom, only the (now complete) parent node.
3. **Set-op CSE** — BestD's bookkeeping recomputes the same unions and
   complements at every step; a set op that repeats an earlier one on
   the same operands is merged into it (about half of a TPC-H Q19 tape).
4. **Dead-code elimination** — BestD's Delta bookkeeping emits ops whose
   results never reach the root Xi; a backward liveness pass drops them.
5. **Slot allocation** — virtual SSA slots are remapped onto a minimal set
   of physical slots by linear scan (a slot is recycled after its last
   read), bounding the device slot buffer ``u32[S, N, W]``.

``PlanTape.key`` hashes the *structure* (op kinds, slots, columns, opcodes)
but not the comparison values, which are passed to the compiled program as a
runtime vector — key-equal tapes (e.g. plan-cache hits with drifted
constants) share one device compilation.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

from .bestd import BestDMachine
from .feedback import group_selectivity
from .plan import Plan
from .predicate import And, Atom, PredicateTree, atom_key, decode_column
from .sets import SetBackend

# op kinds
FULL, EMPTY, ATOM, CHAIN, SETOP = "FULL", "EMPTY", "ATOM", "CHAIN", "SETOP"
# set-op codes — shared with kernels.bitmap_ops
OP_AND, OP_OR, OP_ANDNOT = 0, 1, 2
# comparison opcodes — shared with kernels.ref (LT..NE) and the device
# backend (columnar.device imports this single definition)
CMP_OPCODE = {"lt": 0, "le": 1, "gt": 2, "ge": 3, "eq": 4, "ne": 5}
# dictionary-membership opcode: not a comparison — the atom's value is the
# set of matching dictionary codes and the device backend lowers it to the
# packed-bitmask lookup kernel (kernels.dict_lookup)
IN_OPCODE = 6


def _numeric_value(value) -> bool:
    if isinstance(value, bool):
        return True
    try:
        float(value)
        return True
    except (TypeError, ValueError):
        return False


def device_atom(atom: Atom) -> bool:
    """True iff ``atom`` is a plain comparison a device kernel can run
    (column numeric-ness is only known at bind time, see the backend).

    String atoms rewritten into dictionary code space
    (``columnar.table.rewrite_string_atoms``) are plain numeric comparisons
    over the derived code column, so they pass this predicate and fuse into
    CHAIN groups like any native numeric atom — the tape compiler needs no
    special casing for them.
    """
    return (atom.op in CMP_OPCODE and atom.fn is None
            and _numeric_value(atom.value))


def lookup_atom(atom: Atom) -> bool:
    """True iff ``atom`` is a dictionary-code membership test the device
    dict-lookup kernel executes: ``code_col IN (c0, c1, ...)`` over a
    derived ``#codes`` column with non-negative integer members.  Produced
    by :func:`~repro.core.predicate.codes_expression` when a string atom's
    dictionary hit set fragments into more than ``MAX_CODE_RUNS`` runs
    (regex-shaped LIKE, scattered IN, arbitrary hit masks).  Lookup atoms
    become single ATOM tape ops (they never fuse into CHAIN groups, which
    are comparison-only) and bind to a packed ``u32[ceil(|dict|/32)]`` hit
    bitmask at run time.
    """
    if atom.op != "in" or atom.fn is not None:
        return False
    if decode_column(atom.column) is None:
        return False
    try:
        return all(int(v) == v and int(v) >= 0 for v in atom.value)
    except (TypeError, ValueError):
        return False


def _atom_class(atom: Atom) -> int:
    """Structural-key op class: 0 = host fallback, 1 = comparison kernel,
    2 = dict-lookup kernel.  Part of :attr:`PlanTape.key` because the
    compiled program's per-op lowering differs by class."""
    if lookup_atom(atom):
        return 2
    return 1 if device_atom(atom) else 0


@dataclass(frozen=True)
class TapeOp:
    """One tape instruction (SSA: ``dst`` is written exactly once)."""

    kind: str
    dst: int
    a: int = -1                   # src slot (ATOM/CHAIN) or lhs (SETOP)
    b: int = -1                   # rhs slot (SETOP)
    setop: int = -1               # OP_AND / OP_OR / OP_ANDNOT
    aids: Tuple[int, ...] = ()    # atom ids (1 for ATOM, K for CHAIN)
    conj: bool = True             # CHAIN combine: AND (True) / OR (False)


@dataclass
class PlanTape:
    """A compiled plan: ops + result slot + column/value bindings."""

    tree: PredicateTree
    ops: Tuple[TapeOp, ...]
    result: int
    n_slots: int
    planner: str = ""

    @property
    def n_chains(self) -> int:
        return sum(1 for op in self.ops if op.kind == CHAIN)

    @property
    def n_atom_ops(self) -> int:
        return sum(1 for op in self.ops if op.kind in (ATOM, CHAIN))

    def costed_ops(self) -> Tuple["TapeOp", ...]:
        """ATOM/CHAIN ops in tape order — the ops that pay a column touch.
        Zone-verdict mask rows, feedback observations, and per-op popcount
        bundles are all indexed by position in this sequence."""
        return tuple(op for op in self.ops if op.kind in (ATOM, CHAIN))

    @property
    def key(self) -> tuple:
        """Structural identity (no comparison values): two tapes with equal
        keys run the same device program, so compilations are shared."""
        atoms = self.tree.atoms
        enc = []
        for op in self.ops:
            sig = tuple((atoms[a].column, atoms[a].op,
                         _atom_class(atoms[a])) for a in op.aids)
            enc.append((op.kind, op.dst, op.a, op.b, op.setop, op.conj, sig))
        return (self.planner, self.result, self.n_slots, tuple(enc))

    def describe(self) -> str:
        atoms = self.tree.atoms
        lines = [f"PlanTape[{self.planner}] slots={self.n_slots} "
                 f"ops={len(self.ops)} (chains={self.n_chains})"]
        names = {SETOP: ("AND", "OR", "ANDNOT")}
        for i, op in enumerate(self.ops):
            if op.kind == SETOP:
                lines.append(f"  {i:3d}: s{op.dst} = s{op.a} "
                             f"{names[SETOP][op.setop]} s{op.b}")
            elif op.kind in (ATOM, CHAIN):
                nm = ",".join(atoms[a].name for a in op.aids)
                cc = "" if op.kind == ATOM else (" conj" if op.conj
                                                 else " disj")
                lines.append(f"  {i:3d}: s{op.dst} = {op.kind}({nm}){cc} "
                             f"on s{op.a}")
            else:
                lines.append(f"  {i:3d}: s{op.dst} = {op.kind}")
        lines.append(f"  result: s{self.result}")
        return "\n".join(lines)


def op_observation_meta(tape: PlanTape
                        ) -> List[Tuple["TapeOp", Tuple[Tuple, ...], float]]:
    """Per costed op (ATOM/CHAIN, in tape order): ``(op, atom_keys,
    estimated_fraction)``.

    The estimated fraction is the op's expected output/source ratio under
    the planner's independence assumption — per-atom selectivity for ATOM
    ops, :func:`~repro.core.feedback.group_selectivity` under the chain's
    connective for CHAIN ops.  Backends compare it against the realized
    ``output_popcount / source_popcount`` that rides back with the one host
    sync, producing the per-op Q-Error observations the feedback loop runs
    on.  Pure metadata: consuming it adds no device work.
    """
    atoms = tape.tree.atoms
    out = []
    for op in tape.ops:
        if op.kind not in (ATOM, CHAIN):
            continue
        grp = [atoms[a] for a in op.aids]
        est = group_selectivity([a.selectivity for a in grp], op.conj)
        out.append((op, tuple(atom_key(a) for a in grp), est))
    return out


def _chain_aids(atoms: Sequence[Atom]) -> Tuple[int, ...]:
    """A CHAIN's atom ids in (column, op) order.  A chain evaluates every
    atom on the same source blocks and combines them with one connective,
    so its order is free: chains that differ only in the plan's order get
    one structural key, and so one compiled program."""
    return tuple(a.aid for a in sorted(atoms, key=lambda a: (a.column,
                                                             a.op)))


class _TapeEmitter(SetBackend):
    """Op-recording backend: every call returns a fresh virtual slot id."""

    def __init__(self):
        self.ops: List[TapeOp] = []
        self._next = 0
        self._full: Optional[int] = None
        self._empty: Optional[int] = None

    def _slot(self) -> int:
        s = self._next
        self._next += 1
        return s

    def full(self):
        if self._full is None:
            self._full = self._slot()
            self.ops.append(TapeOp(FULL, self._full))
        return self._full

    def empty(self):
        if self._empty is None:
            self._empty = self._slot()
            self.ops.append(TapeOp(EMPTY, self._empty))
        return self._empty

    def _setop(self, code: int, a: int, b: int) -> int:
        s = self._slot()
        self.ops.append(TapeOp(SETOP, s, a=a, b=b, setop=code))
        return s

    def inter(self, a, b):
        return self._setop(OP_AND, a, b)

    def union(self, a, b):
        return self._setop(OP_OR, a, b)

    def diff(self, a, b):
        return self._setop(OP_ANDNOT, a, b)

    def apply_atom(self, atom: Atom, d):
        s = self._slot()
        self.ops.append(TapeOp(ATOM, s, a=d, aids=(atom.aid,)))
        return s

    def apply_chain(self, atoms: Sequence[Atom], conj: bool, d):
        s = self._slot()
        self.ops.append(TapeOp(CHAIN, s, a=d, aids=_chain_aids(atoms),
                               conj=conj))
        return s

    def count(self, d) -> float:  # pragma: no cover - trace-time guard
        raise RuntimeError("count() during tape tracing: executors on the "
                           "tape path must be data-independent")


def _chain_group(tree: PredicateTree, order: Sequence[int], i: int,
                 applied: frozenset) -> Optional[List[int]]:
    """The maximal fusable group starting at ``order[i]``, or None.

    Fusable = the parent's children are *all* device-evaluable comparison
    atoms, none applied yet, and they occupy ``order[i : i+K]`` exactly.
    """
    aid = order[i]
    atom = tree.atoms[aid]
    parent = tree.parent[id(atom)]
    if parent is None:
        return None
    kids = parent.children
    if len(kids) < 2 or len(kids) > len(order) - i:
        return None
    if not all(isinstance(c, Atom) and device_atom(c) for c in kids):
        return None
    kid_aids = {c.aid for c in kids}
    if kid_aids & applied:
        return None
    run = list(order[i:i + len(kids)])
    if set(run) != kid_aids:
        return None
    return run


def _cse(ops: List[TapeOp], result: int) -> Tuple[List[TapeOp], int]:
    """Common-subexpression elimination of SSA set ops.

    BestD's bookkeeping recomputes the same unions and complements at
    every step (``full \\ (a ∪ b)`` once per atom of a deep tree); a set op
    whose operands resolve to the same values as an earlier one's is
    dropped and its readers read the earlier result.  ``x ∧ x``,
    ``x ∨ x``, ``x ∧ full``, ``x ∨ empty`` and ``x \\ empty`` resolve to
    ``x``.  Only SETOPs merge, so every costed ATOM/CHAIN op — and the
    paper's cost — stays as traced.
    """
    alias = {}
    seen = {}
    full = next((op.dst for op in ops if op.kind == FULL), None)
    empty = next((op.dst for op in ops if op.kind == EMPTY), None)
    kept: List[TapeOp] = []
    for op in ops:
        a, b = alias.get(op.a, op.a), alias.get(op.b, op.b)
        if op.kind != SETOP:
            kept.append(op if a == op.a else TapeOp(
                op.kind, op.dst, a=a, aids=op.aids, conj=op.conj))
            continue
        if op.setop in (OP_AND, OP_OR):
            unit = full if op.setop == OP_AND else empty
            if a == b or b == unit:
                alias[op.dst] = a
                continue
            if a == unit:
                alias[op.dst] = b
                continue
            a, b = min(a, b), max(a, b)
        elif b == empty:
            alias[op.dst] = a
            continue
        key = (op.setop, a, b)
        if key in seen:
            alias[op.dst] = seen[key]
            continue
        seen[key] = op.dst
        kept.append(TapeOp(SETOP, op.dst, a=a, b=b, setop=op.setop))
    return kept, alias.get(result, result)


def _dce(ops: List[TapeOp], result: int) -> List[TapeOp]:
    """Backward liveness: keep only ops whose result reaches ``result``."""
    live = {result}
    kept: List[TapeOp] = []
    for op in reversed(ops):
        if op.dst not in live:
            continue
        kept.append(op)
        if op.kind == SETOP:
            live.add(op.a)
            live.add(op.b)
        elif op.kind in (ATOM, CHAIN):
            live.add(op.a)
    kept.reverse()
    return kept


def _alloc_slots(ops: List[TapeOp], result: int
                 ) -> Tuple[List[TapeOp], int, int]:
    """Linear-scan register allocation of SSA slots onto physical slots."""
    last_use = {result: len(ops)}
    for i, op in enumerate(ops):
        for s in (op.a, op.b):
            if s >= 0:
                last_use[s] = max(last_use.get(s, -1), i)
    phys, free, n_phys = {}, [], 0
    out: List[TapeOp] = []
    for i, op in enumerate(ops):
        reads = [s for s in (op.a, op.b) if s >= 0]
        mapped = {s: phys[s] for s in reads}
        for s in set(reads):
            if last_use.get(s, -1) == i:
                free.append(phys.pop(s))
        if free:
            p = free.pop()
        else:
            p = n_phys
            n_phys += 1
        phys[op.dst] = p
        out.append(TapeOp(op.kind, p,
                          a=mapped.get(op.a, -1), b=mapped.get(op.b, -1),
                          setop=op.setop, aids=op.aids, conj=op.conj))
    return out, phys[result], n_phys


def rebind_tape(tape: PlanTape, tree: PredicateTree,
                aid_map: Sequence[int]) -> PlanTape:
    """Rebind a compiled tape onto a key-equal tree (plan-cache tape reuse).

    ``aid_map[a]`` gives the atom id in ``tree`` playing the role of atom
    ``a`` in the tape's original tree.  Because the plan cache only serves
    trees with equal canonical keys (identical shape under the canonical
    sibling order), the op structure — slots, setops, chain groups — is
    valid verbatim; only the atom ids need remapping.  This skips the whole
    trace / chain-fusion / DCE / slot-allocation pipeline on a cache hit:
    the rebound tape binds its own columns and comparison values at run
    time, and shares the jitted device program whenever its structural
    ``key`` matches (same columns and ops, drifted constants).
    """
    def remap(op):
        aids = [aid_map[a] for a in op.aids]
        if op.kind == CHAIN:
            aids = _chain_aids([tree.atoms[a] for a in aids])
        return TapeOp(op.kind, op.dst, a=op.a, b=op.b, setop=op.setop,
                      aids=tuple(aids), conj=op.conj)

    ops = tuple(remap(op) if op.aids else op for op in tape.ops)
    return PlanTape(tree=tree, ops=ops, result=tape.result,
                    n_slots=tape.n_slots, planner=tape.planner)


def compile_tape(plan: Plan, chain: bool = True) -> PlanTape:
    """Compile ``plan`` into a :class:`PlanTape`.

    ``chain=False`` disables sibling-group fusion (every atom becomes its
    own ATOM op) — useful for differential testing of the CHAIN lowering.
    """
    tree = plan.tree
    em = _TapeEmitter()
    if plan.planner == "nooropt":
        from .nooropt import nooropt_execute
        result = nooropt_execute(tree, em)
    else:
        machine = BestDMachine(tree, em)
        order = plan.order
        i = 0
        while i < len(order):
            grp = (_chain_group(tree, order, i, machine.applied)
                   if chain else None)
            if grp:
                node = tree.parent[id(tree.atoms[grp[0]])]
                d = machine.bestd_region(grp[0])
                sat = em.apply_chain([tree.atoms[g] for g in grp],
                                     isinstance(node, And), d)
                machine.absorb_chain(node, grp, d, sat)
                i += len(grp)
            else:
                machine.apply_step(order[i])
                i += 1
        result = machine.result()
    ops, result = _cse(em.ops, result)
    ops = _dce(ops, result)
    ops, result, n_slots = _alloc_slots(ops, result)
    return PlanTape(tree=tree, ops=tuple(ops), result=result,
                    n_slots=n_slots, planner=plan.planner)
