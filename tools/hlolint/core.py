"""hlolint core: contracts, compiled-artifact checks, baseline, runner.

A Contract names one serving-critical jitted function, a ``build()`` hook
that returns it together with example (or ShapeDtypeStruct) arguments, and
the declared expectations on its COMPILED form. The runner lowers each
contract once (``fn.lower(*args)``), compiles it, and runs the declared
checks against two texts:

- the lowered (pre-optimization) module for the dtype audit — what the
  program ASKS for, before backend-specific rewrites (CPU legalizes bf16
  dots through f32 converts; those are backend noise, a hand-written
  ``.astype(f32)`` on the cache is not);
- the backend-optimized module for alias / transfer / collective checks
  and ``cost_analysis()`` — what XLA actually DID.

Findings are fatal (exit 1) unless waived in the contract itself
(``waivers`` — a reason is mandatory, it lives next to the contract the
way graftlint suppressions live next to the code) or grandfathered in
``tools/hlolint/baseline.json`` (fingerprint + mandatory reason, same
semantics as graftlint's baseline: entries die with the contract/detail
they describe).

Everything here is stdlib + jax; jax itself is imported lazily so the
module can be imported (e.g. by the CLI's --list) without touching the
runtime.
"""

from __future__ import annotations

import hashlib
import json
import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

CHECKS = ("alias", "transfer", "dtype", "collective", "cost")

# meta findings that can be neither waived nor baselined
META_CHECKS = ("build-error", "bad-waiver")

DEFAULT_TOLERANCE = 0.25

# HLO opcodes that move data between host and device. ``-start``/``-done``
# pairs count once (at the -start).
TRANSFER_OPCODES = ("infeed", "outfeed", "send", "recv")

# custom-call targets that smuggle a host round-trip past the opcode check
# (python callbacks, host FFI). Benign compute custom-calls (TopK, LAPACK)
# do not match.
TRANSFER_TARGET_RE = re.compile(r"callback|python|infeed|outfeed|host", re.I)

COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "ragged-all-to-all",
)

# result type is either one shape ("f32[4,8]{1,0}") or a tuple of shapes
# ("(f32[], u32[], token[])" — send/recv/infeed are ALWAYS tuple-typed, and
# the all-reduce combiner can merge same-shape collectives into one
# tuple-shaped op); tuples contain no nested parens, so [^()]* is exact
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?\S+\s*=\s*(?:\([^()]*\)|\S+)\s+([a-z][a-z0-9-]*)\(",
    re.M)
_CUSTOM_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_ALIAS_PARAM_RE = re.compile(r"\(\s*(\d+)\s*,\s*\{\}\s*,\s*(?:may|must)-alias\)")
_TYPE_SIG_RE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")

# numpy dtype name -> HLO primitive type name
_HLO_DTYPES = {
    "float64": "f64", "float32": "f32", "float16": "f16", "bfloat16": "bf16",
    "int64": "s64", "int32": "s32", "int16": "s16", "int8": "s8",
    "uint64": "u64", "uint32": "u32", "uint16": "u16", "uint8": "u8",
    "bool": "pred",
}


def hlo_type_sig(leaf) -> str:
    """'s8[1,24,2,16]'-style signature for a jax array / ShapeDtypeStruct."""
    name = _HLO_DTYPES.get(str(leaf.dtype), str(leaf.dtype))
    return f"{name}[{','.join(str(d) for d in leaf.shape)}]"


@dataclass
class Finding:
    contract: str
    check: str  # one of CHECKS or META_CHECKS
    message: str
    # stable key for fingerprints/waivers: no volatile numbers, just the
    # identity of what broke ("arg1", "all-gather", "flops", a dtype sig)
    detail: str = ""

    def fingerprint(self) -> str:
        key = f"{self.contract}|{self.check}|{self.detail}"
        return hashlib.sha1(key.encode()).hexdigest()[:16]

    def render(self) -> str:
        det = f" [{self.detail}]" if self.detail else ""
        return f"{self.contract}: {self.check}{det}: {self.message}"


@dataclass
class Contract:
    """Declared compiled-form expectations for one jitted hot function.

    build() -> (jitted_fn, args): args may be concrete arrays or
    ShapeDtypeStructs — only shapes/dtypes matter to the checks.
    """

    name: str
    description: str
    build: Callable[[], Tuple[Any, tuple]]
    # call-argument positions whose EVERY leaf buffer must appear in the
    # compiled input_output_alias (donate_argnums that must have fired)
    donated: Tuple[int, ...] = ()
    # match donated leaves to aliased params by dtype only: under GSPMD the
    # entry params carry PER-DEVICE shapes, so global-shape matching would
    # misreport sharded contracts (sharding splits shapes, never dtypes)
    alias_by_dtype: bool = False
    check_transfers: bool = True
    # (regex over the LOWERED module text, why it is forbidden)
    forbid_dtypes: Tuple[Tuple[str, str], ...] = ()
    # (flattened output index, expected HLO dtype name)
    out_dtypes: Tuple[Tuple[int, str], ...] = ()
    # exact count-per-kind budget ({} = no collectives allowed);
    # None skips the check entirely
    collectives: Optional[Dict[str, int]] = None
    # check flops / bytes-accessed against budgets.json under this name
    cost: bool = False
    # lower the module ``forbid_dtypes`` reads for this platform instead of
    # the one the tests run on: where jax lowers an op differently per
    # platform (``ragged_dot`` is one grouped-matmul op for a TPU and a dense
    # masked expansion elsewhere), the promise is about what the chip's
    # compiler is given. Aliases, transfers and costs stay the local compile's
    lowering_platform: Optional[str] = None
    # "check:detail" -> reason; the contract-local analogue of graftlint's
    # inline suppression — the reason is mandatory
    waivers: Dict[str, str] = field(default_factory=dict)


class Artifact:
    """One contract lowered and compiled, with the texts the checks read."""

    def __init__(self, contract: Contract):
        fn, args = contract.build()
        self.args = args
        lowered = fn.lower(*args)
        self.stablehlo = lowered.as_text()
        if contract.lowering_platform:
            self.stablehlo = fn.trace(*args).lower(
                lowering_platforms=(contract.lowering_platform,)).as_text()
        self.compiled = lowered.compile()
        self.hlo = self.compiled.as_text()
        self._header = self.hlo.splitlines()[0] if self.hlo else ""
        self._cost: Optional[Dict[str, float]] = None

    # -- compiled-module parsing ------------------------------------------
    def aliased_param_indices(self) -> List[int]:
        return [int(p) for p in _ALIAS_PARAM_RE.findall(self._header)]

    def _entry_layout(self) -> Tuple[str, str]:
        """(params, results) sections of entry_computation_layout, split by
        balanced-brace scan — layouts like ``{1,0}`` defeat any regex."""
        key = "entry_computation_layout={"
        i = self._header.find(key)
        if i < 0:
            return "", ""
        j = i + len(key)
        depth, k = 1, j
        while k < len(self._header) and depth:
            c = self._header[k]
            if c == "{":
                depth += 1
            elif c == "}":
                depth -= 1
            k += 1
        section = self._header[j:k - 1]
        arrow = section.find(")->")
        if arrow < 0:
            return section, ""
        return section[:arrow + 1], section[arrow + 3:]

    def entry_param_sigs(self) -> List[str]:
        params, _ = self._entry_layout()
        return [f"{t}[{s}]" for t, s in _TYPE_SIG_RE.findall(params)]

    def entry_result_sigs(self) -> List[str]:
        _, results = self._entry_layout()
        return [f"{t}[{s}]" for t, s in _TYPE_SIG_RE.findall(results)]

    def opcode_counts(self) -> Dict[str, int]:
        return opcode_counts_from_text(self.hlo)

    def collective_counts(self) -> Dict[str, int]:
        return collective_counts_from_text(self.hlo)

    def cost(self) -> Dict[str, float]:
        if self._cost is None:
            ca = self.compiled.cost_analysis()
            d = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
            self._cost = {
                "flops": float(d.get("flops", 0.0)),
                "bytes_accessed": float(d.get("bytes accessed", 0.0)),
            }
        return self._cost


def opcode_counts_from_text(hlo: str) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for op in _INSTR_RE.findall(hlo):
        counts[op] = counts.get(op, 0) + 1
    return counts


def collective_counts_from_text(hlo: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for op, n in opcode_counts_from_text(hlo).items():
        base = op[:-6] if op.endswith("-start") else op
        if op.endswith("-done"):
            continue
        if base in COLLECTIVE_KINDS:
            out[base] = out.get(base, 0) + n
    return out


# ----------------------------------------------------------------------
# checks
# ----------------------------------------------------------------------

def check_alias(contract: Contract, art: Artifact) -> List[Finding]:
    """Every leaf buffer of every donated call argument must be aliased to
    an output in the compiled module. XLA silently drops a donation whose
    buffer cannot alias any output (shape/dtype/size mismatch) — the
    program still runs, it just pays the full copy the donation was
    supposed to elide."""
    import jax

    def sig_of(s: str) -> str:
        return s.split("[", 1)[0] if contract.alias_by_dtype else s

    param_sigs = art.entry_param_sigs()
    pool: Dict[str, int] = {}
    for i in art.aliased_param_indices():
        if i < len(param_sigs):
            sig = sig_of(param_sigs[i])
            pool[sig] = pool.get(sig, 0) + 1
    findings: List[Finding] = []
    for argnum in contract.donated:
        missing: Dict[str, int] = {}
        for leaf in jax.tree.leaves(art.args[argnum]):
            sig = sig_of(hlo_type_sig(leaf))
            if pool.get(sig, 0) > 0:
                pool[sig] -= 1
            else:
                missing[sig] = missing.get(sig, 0) + 1
        if missing:
            what = ", ".join(f"{n}x {s}" for s, n in sorted(missing.items()))
            findings.append(Finding(
                contract.name, "alias",
                f"donated arg {argnum}: {what} missing from "
                "input_output_alias — XLA dropped the donation, every call "
                "pays a full copy of those buffers (the PR 2 aliasing "
                "contract; check shapes/shardings of input vs output)",
                detail=f"arg{argnum}"))
    return findings


def check_transfer(contract: Contract, art: Artifact) -> List[Finding]:
    findings: List[Finding] = []
    counts = art.opcode_counts()
    for op, n in sorted(counts.items()):
        base = op[:-6] if op.endswith("-start") else op
        if op.endswith("-done"):
            continue
        if base in TRANSFER_OPCODES:
            findings.append(Finding(
                contract.name, "transfer",
                f"{n}x {base} in the compiled module — a host transfer "
                "inside the hot function stalls the device stream every "
                "call (the HLO twin of graftlint's host-sync rule)",
                detail=base))
    for target in sorted(set(_CUSTOM_TARGET_RE.findall(art.hlo))):
        if TRANSFER_TARGET_RE.search(target):
            findings.append(Finding(
                contract.name, "transfer",
                f"host custom-call {target!r} in the compiled module — a "
                "python/host callback runs on the host once per call, "
                "serializing the decode pipeline",
                detail=target))
    return findings


def check_dtype(contract: Contract, art: Artifact) -> List[Finding]:
    findings: List[Finding] = []
    for pattern, why in contract.forbid_dtypes:
        n = len(re.findall(pattern, art.stablehlo))
        if n:
            findings.append(Finding(
                contract.name, "dtype",
                f"{n}x forbidden dtype signature {pattern!r} in the lowered "
                f"module: {why}",
                detail=pattern))
    if contract.out_dtypes:
        results = art.entry_result_sigs()
        for idx, want in contract.out_dtypes:
            got = results[idx].split("[", 1)[0] if idx < len(results) else "<absent>"
            if got != want:
                findings.append(Finding(
                    contract.name, "dtype",
                    f"output {idx} is {got}, contract requires {want} — a "
                    "widened output dtype doubles that tensor's HBM traffic "
                    "on every call",
                    detail=f"out{idx}"))
    return findings


def check_collective(contract: Contract, art: Artifact) -> List[Finding]:
    budget = contract.collectives or {}
    actual = art.collective_counts()
    findings: List[Finding] = []
    for kind in sorted(set(budget) | set(actual)):
        want, got = budget.get(kind, 0), actual.get(kind, 0)
        if got != want:
            direction = "extra" if got > want else "missing"
            findings.append(Finding(
                contract.name, "collective",
                f"{kind}: compiled module has {got}, contract budgets {want} "
                f"({direction}) — an unbudgeted collective is a reshard the "
                "declared sharding never asked for (ICI time on every step)",
                detail=kind))
    return findings


def check_cost(contract: Contract, art: Artifact, budgets: dict,
               diff_out: Dict[str, dict]) -> List[Finding]:
    actual = art.cost()
    entry = (budgets.get("entries") or {}).get(contract.name)
    tol = float((entry or {}).get(
        "tolerance", budgets.get("tolerance", DEFAULT_TOLERANCE)))
    findings: List[Finding] = []
    record: Dict[str, dict] = {}
    if entry is None:
        findings.append(Finding(
            contract.name, "cost",
            "no committed budget in budgets.json — run "
            "`python -m tools.hlolint --update-budgets`, review the "
            "snapshot, and commit it",
            detail="missing-budget"))
        record = {k: {"actual": v, "budget": None} for k, v in actual.items()}
    else:
        for key, got in actual.items():
            want = float(entry.get(key, 0.0))
            rel = abs(got - want) / max(abs(want), 1.0)
            record[key] = {"actual": got, "budget": want, "rel_delta": rel,
                           "tolerance": tol}
            if rel > tol:
                findings.append(Finding(
                    contract.name, "cost",
                    f"{key} drifted {rel:+.1%} past the ±{tol:.0%} band "
                    f"(budget {want:,.0f}, compiled {got:,.0f}) — the PR 2/3 "
                    "bandwidth wins are CI invariants; if the change is "
                    "intentional, re-baseline with --update-budgets and say "
                    "why in the commit",
                    detail=key))
    diff_out[contract.name] = record
    return findings


# ----------------------------------------------------------------------
# budgets + baseline
# ----------------------------------------------------------------------

def load_budgets(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def save_budgets(path: str, measured: Dict[str, Dict[str, float]],
                 previous: Optional[dict] = None) -> None:
    previous = previous or {}
    entries = dict(previous.get("entries") or {})
    for name, cost in measured.items():
        old = dict(entries.get(name) or {})
        old.update({k: round(v, 1) for k, v in cost.items()})
        entries[name] = old
    payload = {
        "_comment": "hlolint compiled-cost budgets (flops / bytes accessed "
                    "per contract, from HLO cost analysis under "
                    "JAX_PLATFORMS=cpu + the virtual 8-device mesh). "
                    "Re-baseline ONLY for intentional changes: "
                    "python -m tools.hlolint --update-budgets, then review "
                    "the diff — see docs/static-analysis.md.",
        "tolerance": previous.get("tolerance", DEFAULT_TOLERANCE),
        "entries": {k: entries[k] for k in sorted(entries)},
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")


def load_baseline(path: str) -> Dict[str, dict]:
    """fingerprint -> entry; ValueError on reason-less entries. The file
    format and validation ARE graftlint's (one validator, one auditability
    bar) — only the fingerprint contents differ (contract|check|detail
    instead of rule|path|function|line)."""
    from tools.graftlint.core import load_baseline as _graftlint_load

    return _graftlint_load(path)


def apply_baseline(findings: Sequence[Finding], baseline: Dict[str, dict]):
    budget = {fp: e.get("count", 1) for fp, e in baseline.items()}
    reported: List[Finding] = []
    absorbed: List[Finding] = []
    for f in findings:
        fp = f.fingerprint()
        if f.check in CHECKS and budget.get(fp, 0) > 0:
            budget[fp] -= 1
            absorbed.append(f)
        else:
            reported.append(f)
    return reported, absorbed


# ----------------------------------------------------------------------
# runner
# ----------------------------------------------------------------------

def run_contracts(
    contracts: Sequence[Contract],
    budgets: Optional[dict] = None,
    baseline: Optional[Dict[str, dict]] = None,
    checks: Optional[Sequence[str]] = None,
    jobs: int = 1,
):
    """Lower+compile each contract and run its declared checks.

    Returns (reported, absorbed, waived, budget_diff, measured_costs).
    ``reported`` non-empty => the gate fails. ``measured_costs`` holds the
    compiled cost of every cost-checked contract (for --update-budgets).

    ``jobs > 1`` builds the Artifacts (the expensive lower+compile step)
    in a thread pool — XLA compilation releases the GIL, and the lazy
    contract fixtures are lock-guarded (tools/hlolint/contracts.py) —
    then runs the checks serially in declaration order, so findings and
    budget diffs are byte-identical to the serial run.
    """
    active = set(checks or CHECKS)
    unknown = active - set(CHECKS)
    if unknown:
        raise ValueError(f"unknown check(s): {', '.join(sorted(unknown))}")
    budgets = budgets or {}
    baseline = baseline or {}
    findings: List[Finding] = []
    waived: List[Finding] = []
    budget_diff: Dict[str, dict] = {}
    measured: Dict[str, Dict[str, float]] = {}

    prebuilt: Dict[str, object] = {}
    if jobs > 1 and len(contracts) > 1:
        from concurrent.futures import ThreadPoolExecutor

        def build(c):
            try:
                return Artifact(c)
            except Exception as e:  # noqa: BLE001 — reported per contract below
                return e

        # The first contract is built alone to warm the shared lazy
        # fixtures (base server, TP server, batcher): building it inside
        # the pool would just park every worker on the fixture lock
        # behind one loader, paying thread overhead for no overlap.
        prebuilt[contracts[0].name] = build(contracts[0])
        rest = contracts[1:]
        with ThreadPoolExecutor(max_workers=min(int(jobs), len(rest))) as pool:
            for c, art in zip(rest, pool.map(build, rest)):
                prebuilt[c.name] = art

    for contract in contracts:
        for key, reason in contract.waivers.items():
            if not str(reason).strip():
                findings.append(Finding(
                    contract.name, "bad-waiver",
                    f"waiver {key!r} has no reason — the reason is "
                    "mandatory, it is the audit trail",
                    detail=key))
        try:
            art = prebuilt.get(contract.name)
            if art is None:
                art = Artifact(contract)
            elif isinstance(art, Exception):
                raise art
        except Exception as e:  # noqa: BLE001 — any build/lower/compile failure is the finding
            findings.append(Finding(
                contract.name, "build-error",
                f"contract failed to build/lower/compile: "
                f"{type(e).__name__}: {e}",
                detail="build"))
            continue
        local: List[Finding] = []
        if "alias" in active and contract.donated:
            local.extend(check_alias(contract, art))
        if "transfer" in active and contract.check_transfers:
            local.extend(check_transfer(contract, art))
        if "dtype" in active and (contract.forbid_dtypes or contract.out_dtypes):
            local.extend(check_dtype(contract, art))
        if "collective" in active and contract.collectives is not None:
            local.extend(check_collective(contract, art))
        if "cost" in active and contract.cost:
            local.extend(check_cost(contract, art, budgets, budget_diff))
            measured[contract.name] = art.cost()
        for f in local:
            reason = contract.waivers.get(f"{f.check}:{f.detail}", "").strip()
            if reason:
                waived.append(f)
            else:
                findings.append(f)

    reported, absorbed = apply_baseline(findings, baseline)
    reported.sort(key=lambda f: (f.contract, f.check, f.detail))
    return reported, absorbed, waived, budget_diff, measured
