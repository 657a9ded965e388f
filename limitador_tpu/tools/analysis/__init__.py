"""Pass-registry static-analysis framework (ISSUE 9).

The reference gets data-race freedom and API-misuse checks from the
Rust compiler; this stack spans three concurrency domains with no
compiler help — relaxed-atomics C++ in ``native/``, multi-loop async
Python, and donated JAX kernels where one stray host sync blows the 2ms
p99 budget. This package is the correctness tooling that earns the
equivalent: the five ad-hoc passes that used to live in
``tools/lint.py`` (style, metric-registry, donation, ctypes-ABI drift,
native-phase / debug-section cross-checks) ported onto one registry,
plus the analyzers the hot path actually needs:

* ``lock-order`` — the acquisition graph over the storage lock,
  native-lane lock, broker lock and observatory lock, extracted from
  the AST: cycles are rejected, ``await``/blocking calls while holding
  a threading lock are flagged, and the observatory drain thread's
  storage-lock hold is allowlisted EXPLICITLY (citing its perf-smoke
  budget), not silently passed.
* ``buffer-safety`` — ctypes calls into the GIL-released ``hp_*`` /
  ``h2i_*`` exports whose numpy buffer arguments are temporaries that
  die before the call returns.
* ``tracing-safety`` — hot-path modules must not host-sync on the
  decision path (``block_until_ready``, implicit ``np.asarray``),
  kernel launches must ride the pow2-quantizing owner modules, and
  ``shard_map`` sites are donation-checked.

Model: each pass is a function ``run(ctx) -> List[Finding]`` registered
under a name. ``python -m limitador_tpu.tools.analysis`` runs them all
(``--list`` / ``--only`` / ``--json`` for CI), exit 1 on any active
finding. ``baseline.txt`` (checked in, EMPTY at HEAD) suppresses known
findings during a migration without losing them — suppressed findings
stay visible in ``--json`` and ``--show-suppressed``. ``# noqa`` on the
offending line suppresses single style findings, as before.

``tools/lint.py`` remains as a thin compatibility shim over this
package, so ``make lint``, ``tests/test_lint.py`` and every docstring
that says "tools/lint.py" keep working.
"""

from __future__ import annotations

import ast
import dataclasses
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "Finding",
    "AnalysisPass",
    "PASSES",
    "RepoContext",
    "register_pass",
    "run_passes",
    "load_baseline",
    "finding_key",
    "repo_root",
    "DEFAULT_TARGETS",
    "BASELINE_REL",
]

DEFAULT_TARGETS = ("limitador_tpu", "tests", "bench.py",
                   "chip_smoke.py", "__graft_entry__.py")

#: the checked-in baseline/suppression file, repo-relative. Empty at
#: HEAD (tests/test_analysis.py asserts it): a finding lands here only
#: while a migration is in flight, with a dated comment saying why.
BASELINE_REL = "limitador_tpu/tools/analysis/baseline.txt"


@dataclasses.dataclass
class Finding:
    """One analyzer finding: where, what, and how to fix it."""

    pass_name: str
    path: str       #: repo-relative posix path (absolute when outside)
    line: int
    message: str
    hint: str = ""
    #: set when a baseline entry or a pass allowlist suppressed it —
    #: carries the reason, so a suppression is never silent
    suppressed_by: Optional[str] = None

    def render(self) -> str:
        out = f"{self.path}:{self.line}: [{self.pass_name}] {self.message}"
        if self.hint:
            out += f"\n    fix: {self.hint}"
        if self.suppressed_by:
            out += f"\n    suppressed: {self.suppressed_by}"
        return out

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def finding_key(finding: Finding) -> str:
    """Baseline key: line-number-insensitive so unrelated edits above a
    baselined finding don't resurrect it."""
    return f"{finding.pass_name}|{finding.path}|{finding.message}"


@dataclasses.dataclass(frozen=True)
class AnalysisPass:
    name: str
    description: str
    run: Callable[["RepoContext"], List[Finding]]
    #: fast passes ride tier-1 (the <10s perf-smoke budget); slow ones
    #: (none today — the sanitizer race hunt lives in pytest) only run
    #: with --all-slow
    fast: bool = True


#: name -> pass, in registration order (determines run + report order)
PASSES: Dict[str, AnalysisPass] = {}


def register_pass(name: str, description: str, fast: bool = True):
    def wrap(fn):
        PASSES[name] = AnalysisPass(name, description, fn, fast)
        return fn
    return wrap


def repo_root() -> Path:
    return Path(__file__).resolve().parents[3]


class RepoContext:
    """Shared walkers for every pass: one parse per file per run, repo-
    relative paths, target iteration and ``# noqa`` suppression."""

    def __init__(self, root, targets: Optional[Sequence] = None):
        self.root = Path(root).resolve()
        self.targets = tuple(str(t) for t in (targets or DEFAULT_TARGETS))
        self._sources: Dict[Path, str] = {}
        self._trees: Dict[Path, Optional[ast.AST]] = {}
        self._nodes: Dict[Path, List[ast.AST]] = {}
        self._files: Optional[List[Path]] = None

    # -- paths ---------------------------------------------------------------

    def rel(self, path) -> str:
        path = Path(path)
        try:
            return path.resolve().relative_to(self.root).as_posix()
        except ValueError:
            return str(path)

    def path(self, rel: str) -> Path:
        return self.root / rel

    # -- cached reads --------------------------------------------------------

    def source(self, path) -> str:
        path = Path(path)
        if path not in self._sources:
            try:
                self._sources[path] = path.read_text()
            except OSError:
                self._sources[path] = ""
        return self._sources[path]

    def lines(self, path) -> List[str]:
        return self.source(path).splitlines()

    def tree(self, path) -> Optional[ast.AST]:
        """Parsed AST, or None on syntax error / missing file (the
        style pass reports syntax errors; every other pass skips)."""
        path = Path(path)
        if path not in self._trees:
            src = self.source(path)
            try:
                self._trees[path] = ast.parse(src, filename=str(path))
            except SyntaxError:
                self._trees[path] = None
        return self._trees[path]

    def nodes(self, path) -> List[ast.AST]:
        """Flattened node list of ``tree(path)``, cached — ``ast.walk``
        re-traverses the tree per call, and with nine passes over the
        same files the traversal dominates the gate's runtime."""
        path = Path(path)
        if path not in self._nodes:
            tree = self.tree(path)
            self._nodes[path] = [] if tree is None else list(ast.walk(tree))
        return self._nodes[path]

    def noqa(self, path, lineno: int) -> bool:
        lines = self.lines(path)
        return 0 < lineno <= len(lines) and "# noqa" in lines[lineno - 1]

    # -- iteration -----------------------------------------------------------

    def iter_files(self) -> List[Path]:
        """The lintable target set (style/buffer/tracing walk this);
        generated protobuf output is excluded — protoc's style, not
        ours."""
        if self._files is None:
            files: List[Path] = []
            for target in self.targets:
                p = Path(target)
                if not p.is_absolute():
                    p = self.root / target
                if p.is_dir():
                    files.extend(sorted(p.rglob("*.py")))
                elif p.suffix == ".py" and p.exists():
                    files.append(p)
            self._files = [
                f for f in files
                if not f.name.endswith("_pb2.py")
                and not f.name.endswith("_pb2_grpc.py")
            ]
        return self._files

    def package_files(self, rel_prefix: str = "limitador_tpu") -> List[Path]:
        pkg = self.root / rel_prefix
        if not pkg.is_dir():
            return []
        return [
            f for f in sorted(pkg.rglob("*.py"))
            if not f.name.endswith("_pb2.py")
            and not f.name.endswith("_pb2_grpc.py")
        ]

    # -- shared AST helpers ---------------------------------------------------

    def module_string_tuple(self, path, name: str) -> List[str]:
        """Entries of a module-level ``NAME = ("a", "b", ...)``
        tuple/list assignment (string constants only)."""
        tree = self.tree(path)
        if tree is None:
            return []
        out: List[str] = []
        for node in tree.body:
            if not (
                isinstance(node, ast.Assign)
                and any(
                    isinstance(t, ast.Name) and t.id == name
                    for t in node.targets
                )
                and isinstance(node.value, (ast.Tuple, ast.List))
            ):
                continue
            for elt in node.value.elts:
                if isinstance(elt, ast.Constant) and isinstance(
                    elt.value, str
                ):
                    out.append(elt.value)
        return out


# -- baseline ----------------------------------------------------------------

def load_baseline(root: Path) -> Dict[str, str]:
    """key -> reason from the checked-in baseline file. Format: one
    finding key per line (``pass|path|message``), ``#`` comments; a
    trailing `` -- reason`` documents why it's parked."""
    path = Path(root) / BASELINE_REL
    out: Dict[str, str] = {}
    try:
        text = path.read_text()
    except OSError:
        return out
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        key, _sep, reason = line.partition(" -- ")
        out[key.strip()] = reason.strip() or "baselined"
    return out


def run_passes(
    root=None,
    names: Optional[Sequence[str]] = None,
    targets: Optional[Sequence] = None,
    use_baseline: bool = True,
) -> Tuple[List[Finding], List[Finding]]:
    """Run the selected passes (all registered when ``names`` is None)
    and split findings into (active, suppressed). Unknown pass names
    raise KeyError — the CLI maps that to exit 2."""
    root = Path(root) if root is not None else repo_root()
    ctx = RepoContext(root, targets)
    selected = list(names) if names else list(PASSES)
    findings: List[Finding] = []
    for name in selected:
        findings.extend(PASSES[name].run(ctx))
    baseline = load_baseline(root) if use_baseline else {}
    active: List[Finding] = []
    suppressed: List[Finding] = []
    for f in findings:
        if f.suppressed_by is None and baseline:
            reason = baseline.get(finding_key(f))
            if reason is not None:
                f.suppressed_by = f"baseline: {reason}"
        (suppressed if f.suppressed_by else active).append(f)
    return active, suppressed


# Pass modules register themselves on import; order here is report
# order (cheap structural passes first, the graph analyzers last).
from . import style           # noqa: E402  (registration import)
from . import registries      # noqa: E402
from . import donation        # noqa: E402
from . import native_abi      # noqa: E402
from . import buffer_safety   # noqa: E402
from . import lock_order      # noqa: E402
from . import tracing         # noqa: E402
