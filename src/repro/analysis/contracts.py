"""Declarative performance contracts over lowered/compiled modules + jaxprs.

A contract is a small object with a ``check(EntryArtifacts) -> [Violation]``
method.  Empty list = the invariant holds; every violation carries an
actionable message naming the offending instruction/equation.  The lint CLI
(`repro.analysis.lint`) binds suites of these to the repo's real entry
points; the test gates assert through the same objects (and the census
helpers re-exported here) instead of hand-rolled regexes.

Contracts:

  * :class:`CollectiveCensus` — exact/max per-kind collective counts plus
    shape-predicate matchers (e.g. "exactly one interface-sized
    all-reduce", "zero of them on the neighbour path").
  * :class:`WireWidth` — element dtypes of collective-permutes in the
    LOWERED StableHLO (the width the repo constructs; CPU's compiled
    modules hoist the converts, so the lowered module is the truth).
  * :class:`AccumulationDtype` — jaxpr-level: no sub-fp32 float
    accumulation in ``dot_general`` / ``reduce_sum`` / ``scatter-add``
    (the PR 8 root-fix class, enforced everywhere).
  * :class:`NoF64Leak` — no f64 buffers in the module.
  * :class:`NoHostTransfer` — no infeed/outfeed/host sends in compiled HLO.
  * :class:`VmemBudget` — a Pallas block configuration fits the tune.py
    VMEM model.
  * :class:`NoRetrace` — a serving trace counter did not move.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

from repro.analysis import hlo_ir
from repro.analysis.hlo_ir import (  # noqa: F401  (re-exported for gates)
    collective_census,
    interface_allreduce_count,
    wire_dtypes,
)

__all__ = [
    "Violation", "EntryArtifacts", "Contract", "check_suite",
    "CollectiveCensus", "ShapeCount", "interface_allreduce",
    "WireWidth", "AccumulationDtype", "NoF64Leak", "NoHostTransfer",
    "VmemBudget", "NoRetrace",
    "collective_census", "interface_allreduce_count", "wire_dtypes",
]


@dataclass
class Violation:
    contract: str
    entry: str
    message: str

    def __str__(self) -> str:
        return f"[{self.contract}] {self.entry}: {self.message}"


@dataclass
class EntryArtifacts:
    """Everything a contract may inspect for one entry point.

    Any field may be None — a contract that needs a missing artifact
    reports that as a violation rather than silently passing.
    """

    name: str = ""
    lowered_text: Optional[str] = None
    compiled_text: Optional[str] = None
    jaxpr: Optional[Any] = None          # jax ClosedJaxpr
    meta: Dict[str, Any] = field(default_factory=dict)


class Contract:
    name = "contract"

    def check(self, art: EntryArtifacts) -> List[Violation]:
        raise NotImplementedError

    def _v(self, art: EntryArtifacts, message: str) -> Violation:
        return Violation(self.name, art.name, message)

    def _need(self, art: EntryArtifacts, attr: str) -> Optional[Violation]:
        if getattr(art, attr) is None:
            return self._v(art, f"missing artifact '{attr}' "
                                f"(entry did not provide it)")
        return None


def check_suite(art: EntryArtifacts,
                contracts: Iterable[Contract]) -> List[Violation]:
    out: List[Violation] = []
    for c in contracts:
        out.extend(c.check(art))
    return out


# ----------------------------------------------------- collective census ---


@dataclass
class ShapeCount:
    """Count collectives of `kind` whose instruction matches `pred`.

    `exact`/`max_count` bound the count; `exact=0` forbids the shape
    outright (violations then name every matching instruction).
    """

    label: str
    kind: str
    pred: Callable[[hlo_ir.Instruction], bool]
    exact: Optional[int] = None
    max_count: Optional[int] = None


def interface_allreduce(n_shared: int, nrhs: Optional[int] = None,
                        dtype: str = "f32", exact: Optional[int] = None,
                        max_count: Optional[int] = None) -> ShapeCount:
    """Matcher for all-reduces over interface-sized buffers — the shape
    predicate the psum/neighbour gates share.  `nrhs` semantics match
    :func:`hlo_ir.interface_allreduce_count`."""
    def pred(i: hlo_ir.Instruction) -> bool:
        if i.dtype != dtype:
            return False
        dims = i.dims
        if nrhs is None:
            return bool(dims) and dims[0] == n_shared
        if nrhs == 1:
            return dims == [n_shared]
        return dims == [n_shared, nrhs]

    tag = f"{dtype}[{n_shared}" + ("" if nrhs in (None, 1) else f",{nrhs}") \
        + ("]" if nrhs is not None else ",...]")
    return ShapeCount(f"interface all-reduce {tag}", "all-reduce", pred,
                      exact=exact, max_count=max_count)


class CollectiveCensus(Contract):
    """Per-kind collective counts on the COMPILED module (async pairs
    counted once), plus shape-predicate matchers."""

    name = "collective-census"

    def __init__(self, exact: Optional[Dict[str, int]] = None,
                 max_counts: Optional[Dict[str, int]] = None,
                 min_counts: Optional[Dict[str, int]] = None,
                 matchers: Sequence[ShapeCount] = ()):
        self.exact = dict(exact or {})
        self.max_counts = dict(max_counts or {})
        self.min_counts = dict(min_counts or {})
        self.matchers = list(matchers)

    def check(self, art: EntryArtifacts) -> List[Violation]:
        miss = self._need(art, "compiled_text")
        if miss:
            return [miss]
        txt = art.compiled_text
        census = hlo_ir.collective_census(txt)
        out: List[Violation] = []
        for kind, want in self.exact.items():
            got = census.get(kind, 0)
            if got != want:
                out.append(self._v(art, f"expected exactly {want} "
                                        f"{kind}, compiled module has "
                                        f"{got}"))
        for kind, cap in self.max_counts.items():
            got = census.get(kind, 0)
            if got > cap:
                out.append(self._v(art, f"expected at most {cap} {kind}, "
                                        f"compiled module has {got}"))
        for kind, floor in self.min_counts.items():
            got = census.get(kind, 0)
            if got < floor:
                out.append(self._v(art, f"expected at least {floor} "
                                        f"{kind}, compiled module has "
                                        f"{got}"))
        if self.matchers:
            mod = hlo_ir.HloModule.parse(txt)
            for m in self.matchers:
                hits = [(c, i) for c, i in mod.collectives(pairs_once=True)
                        if i.base_opcode == m.kind and m.pred(i)]
                n = len(hits)
                names = ", ".join(
                    f"%{i.name} = {i.type_str} {i.opcode} (in %{c})"
                    for c, i in hits[:4])
                if m.exact is not None and n != m.exact:
                    detail = f" — offending: {names}" if hits else ""
                    out.append(self._v(
                        art, f"expected exactly {m.exact} x {m.label}, "
                             f"found {n}{detail}"))
                elif m.max_count is not None and n > m.max_count:
                    out.append(self._v(
                        art, f"expected at most {m.max_count} x {m.label}, "
                             f"found {n} — offending: {names}"))
        return out


# ------------------------------------------------------------ wire width ---


class WireWidth(Contract):
    """Element dtypes of `kind` collectives in the LOWERED module.

    `require`: dtypes (HLO spelling — s8, bf16) that MUST appear;
    `allowed`: if given, every observed dtype must be in it.  Observed
    StableHLO spellings are normalized (i8 -> s8) before comparison.
    """

    name = "wire-width"

    def __init__(self, require: Iterable[str] = (),
                 allowed: Optional[Iterable[str]] = None,
                 kind: str = "collective-permute"):
        self.require = set(require)
        self.allowed = None if allowed is None else set(allowed)
        self.kind = kind

    def check(self, art: EntryArtifacts) -> List[Violation]:
        miss = self._need(art, "lowered_text")
        if miss:
            return [miss]
        got = set(hlo_ir.wire_dtypes(art.lowered_text, kind=self.kind,
                                     normalize=True))
        out: List[Violation] = []
        for dt in sorted(self.require - got):
            out.append(self._v(
                art, f"no {self.kind} ships {dt} in the lowered module "
                     f"(observed wire dtypes: {sorted(got) or 'none'}) — "
                     f"the reduced-width wire was lost before XLA"))
        if self.allowed is not None:
            for dt in sorted(got - self.allowed):
                out.append(self._v(
                    art, f"{self.kind} ships {dt}, outside the allowed "
                         f"wire set {sorted(self.allowed)}"))
        return out


# ---------------------------------------------------- accumulation dtype ---


def _walk_eqns(jaxpr):
    """Depth-first over every equation, descending into sub-jaxprs
    (pjit, while/scan/cond bodies, shard_map, custom_*)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _param_jaxprs(eqn.params):
            yield from _walk_eqns(sub)


def _param_jaxprs(params):
    for v in params.values():
        for j in _as_jaxprs(v):
            yield j


def _as_jaxprs(v):
    if hasattr(v, "jaxpr") and hasattr(v, "consts"):   # ClosedJaxpr
        yield v.jaxpr
    elif hasattr(v, "eqns"):                           # Jaxpr
        yield v
    elif isinstance(v, (list, tuple)):
        for u in v:
            yield from _as_jaxprs(u)


def _src_line(eqn) -> str:
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return "<unknown source>"


def _is_low_float(dtype) -> bool:
    import jax.numpy as jnp
    try:
        return jnp.issubdtype(dtype, jnp.floating) \
            and jnp.finfo(dtype).bits < 32
    except Exception:
        return False


class AccumulationDtype(Contract):
    """No sub-fp32 float accumulation anywhere in the jaxpr.

    Flags ``dot_general`` whose accumulation dtype (the
    `preferred_element_type`, or the result dtype when unset) is a
    float narrower than 32 bits, and ``reduce_sum`` / ``scatter-add``
    reducing sub-fp32 floats.  Storage in bf16 is fine; *summing* in
    bf16 is the PR 8 bug class this forbids.
    """

    name = "accumulation-dtype"
    _PRIMS = ("dot_general", "reduce_sum", "scatter-add")

    def check(self, art: EntryArtifacts) -> List[Violation]:
        miss = self._need(art, "jaxpr")
        if miss:
            return [miss]
        out: List[Violation] = []
        closed = art.jaxpr
        jaxpr = getattr(closed, "jaxpr", closed)
        for eqn in _walk_eqns(jaxpr):
            p = eqn.primitive.name
            if p not in self._PRIMS:
                continue
            if p == "dot_general":
                acc = eqn.params.get("preferred_element_type")
                if acc is None:
                    acc = eqn.outvars[0].aval.dtype
                if _is_low_float(acc):
                    lhs, rhs = (v.aval for v in eqn.invars[:2])
                    out.append(self._v(
                        art,
                        f"dot_general accumulates in {acc} "
                        f"({lhs.str_short()} x {rhs.str_short()}) at "
                        f"{_src_line(eqn)} — set "
                        f"preferred_element_type=float32 and round the "
                        f"result once"))
            else:
                red = eqn.invars[0].aval.dtype
                if _is_low_float(red):
                    out.append(self._v(
                        art,
                        f"{p} reduces {eqn.invars[0].aval.str_short()} at "
                        f"{p}-width {red} at {_src_line(eqn)} — promote to "
                        f"f32 for the sum and round once"))
        return out


# ------------------------------------------------------------- f64 / host --


class NoF64Leak(Contract):
    """No f64 buffer anywhere in the module (either dialect) — a double
    sneaking in silently makes every MXU path 8x slower."""

    name = "no-f64-leak"

    def check(self, art: EntryArtifacts) -> List[Violation]:
        txt = art.compiled_text or art.lowered_text
        if txt is None:
            return [self._v(art, "missing artifact: needs compiled_text "
                                 "or lowered_text")]
        out: List[Violation] = []
        if hlo_ir._is_mlir(txt):
            for m in re.finditer(r"tensor<(?:[\dx?]+x)?f64>", txt):
                out.append(self._v(art, f"f64 tensor in lowered module: "
                                        f"{m.group(0)}"))
                break  # one representative is actionable enough
            return out
        for cname, i in hlo_ir.HloModule.parse(txt).instructions():
            if i.dtype == "f64":
                out.append(self._v(
                    art, f"f64 buffer: %{i.name} = {i.type_str} {i.opcode} "
                         f"(in %{cname})"))
        return out[:4]


class NoHostTransfer(Contract):
    """No host round-trips in compiled HLO: infeed/outfeed/host
    send/recv or host callbacks stall the device pipeline."""

    name = "no-host-transfer"
    _OPS = {"infeed", "outfeed", "send", "recv", "send-done", "recv-done"}
    _CALLBACKS = ("xla_python_cpu_callback", "xla_ffi_python_cpu_callback",
                  "callback")

    def check(self, art: EntryArtifacts) -> List[Violation]:
        miss = self._need(art, "compiled_text")
        if miss:
            return [miss]
        out: List[Violation] = []
        for cname, i in hlo_ir.HloModule.parse(art.compiled_text) \
                .instructions():
            hit = i.opcode in self._OPS \
                or "is_host_transfer=true" in i.rest \
                or (i.opcode == "custom-call"
                    and any(cb in i.rest for cb in self._CALLBACKS))
            if hit:
                out.append(self._v(
                    art, f"host transfer: %{i.name} = {i.type_str} "
                         f"{i.opcode} (in %{cname})"))
        return out[:4]


# ------------------------------------------------------------ vmem budget --


class VmemBudget(Contract):
    """The Pallas block configuration fits the autotuner's VMEM model
    (`kernels.axhelm.tune.block_vmem_bytes` vs `VMEM_BUDGET_BYTES`) —
    the enforcement point of the v3 model in kernels/axhelm/DESIGN.md."""

    name = "vmem-budget"

    def __init__(self, variant: str, n1: int, d: int, dtype,
                 block_elems: int, helmholtz: bool = False, nrhs: int = 1,
                 budget: Optional[int] = None):
        self.variant = variant
        self.n1 = n1
        self.d = d
        self.dtype = dtype
        self.block_elems = block_elems
        self.helmholtz = helmholtz
        self.nrhs = nrhs
        self.budget = budget

    def check(self, art: EntryArtifacts) -> List[Violation]:
        from repro.kernels.axhelm import tune
        budget = tune.VMEM_BUDGET_BYTES if self.budget is None else \
            self.budget
        need = tune.block_vmem_bytes(self.variant, self.n1, self.d,
                                     self.dtype, self.block_elems,
                                     self.helmholtz, nrhs=self.nrhs)
        if need > budget:
            return [self._v(
                art, f"axhelm[{self.variant}] block_elems="
                     f"{self.block_elems} (n1={self.n1}, d={self.d}, "
                     f"dtype={self.dtype}, helmholtz={self.helmholtz}, "
                     f"nrhs={self.nrhs}) needs {need} B of VMEM, over the "
                     f"{budget} B budget — shrink the block or re-tune")]
        return []


# -------------------------------------------------------------- no-retrace --


class NoRetrace(Contract):
    """A serving trace counter did not move: `meta['traces_before']` ==
    `meta['traces_after']` (the bucket cache replayed, never retraced)."""

    name = "no-retrace"

    def check(self, art: EntryArtifacts) -> List[Violation]:
        before = art.meta.get("traces_before")
        after = art.meta.get("traces_after")
        if before is None or after is None:
            return [self._v(art, "missing meta: needs traces_before and "
                                 "traces_after")]
        if after != before:
            return [self._v(
                art, f"trace counter moved {before} -> {after}: "
                     f"{after - before} post-warmup compilation(s) — a "
                     f"request pattern missed the warmed bucket ladder")]
        return []

    @classmethod
    def counts(cls, before: int, after: int,
               entry: str = "") -> List[Violation]:
        """One-liner for test gates: violations iff the counter moved."""
        art = EntryArtifacts(name=entry, meta={"traces_before": before,
                                               "traces_after": after})
        return cls().check(art)
