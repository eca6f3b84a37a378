"""The feature-interaction compatibility matrix — ONE declarative table.

Every unsupported feature composition in the stack (secure aggregation x
compression, tree x no-wait, serving x anything lossy, ...) used to be a
hand-copied ``raise`` scattered across the executor, the trainer, the
launcher, the workers, and the serving driver.  This module is the single
source of truth: each :class:`CompatRule` names the interacting features,
the REASON the composition is unsound, and the layers that must reject it.
Every layer rejects *through* :func:`check`, so a rule added here is
enforced everywhere it declares — and ``repro.analysis`` statically proves
each declared layer actually calls :func:`check` with the rule's feature
flags (rule C001), so an enforcement layer cannot silently drop out.

Layers (see :data:`LAYER_MODULES` for the module each name maps to):

* ``config``   — :class:`repro.configs.base.VerticalConfig` validation
* ``schedule`` — ``step_schedule`` / ``serve_schedule`` construction
* ``engine``   — the discrete-event simulators' ``StepPlan`` builders
* ``executor`` — :class:`repro.runtime.executor.Executor` construction
* ``worker``   — :class:`repro.transport.base.TowerWorker` (the privacy
  principal's own guard: it must not trust the driver)
* ``train``    — ``repro.train.loop.train_split`` (before workers spawn)
* ``launch``   — the CLI launcher (flag-named ``SystemExit``)
* ``serve``    — :class:`repro.serve.split_serve.SplitLMServer`

The matrix renders to markdown via :func:`render_markdown`; the committed
copy lives at ``docs/compat_matrix.md`` (linter rule D001 flags drift).
"""
from __future__ import annotations

from dataclasses import dataclass

#: merges with a partial-sum regrouping / mask-cancelling sum
ADDITIVE_MERGES = ("sum", "avg")

#: enforcement-layer name -> the module whose source must call check()
#: with the rule's feature flags (consumed by repro.analysis rule C001)
LAYER_MODULES = {
    "config": "src/repro/configs/base.py",
    "schedule": "src/repro/core/protocol.py",
    "engine": "src/repro/runtime/engine.py",
    "executor": "src/repro/runtime/executor.py",
    "worker": "src/repro/transport/base.py",
    "train": "src/repro/train/loop.py",
    "launch": "src/repro/launch/train.py",
    "serve": "src/repro/serve/split_serve.py",
}

#: feature name -> the check() keyword that carries it (identity unless
#: the feature is derived, like nonadditive from the merge string)
FEATURE_KWARGS = {
    "secure": "secure",
    "compress": "compress",
    "tree": "tree",
    "nowait": "nowait",
    "merge_fn": "merge_fn",
    "nonadditive": "merge",
    "serve": "serve",
}

#: feature name -> how the CLI launcher names it in a SystemExit
CLI_NAMES = {
    "secure": "--secure-agg",
    "compress": "--compress",
    "tree": "--agg-tree-fanout",
    "nowait": "--runtime nowait",
    "merge_fn": "a program merge_fn",
    "nonadditive": "a non-additive merge",
    "serve": "serving",
}


@dataclass(frozen=True)
class CompatRule:
    """One unsound feature composition.

    ``features`` is ordered: the launcher phrases its SystemExit as
    "<flag of features[0]> cannot run with <flag of features[1]>".
    ``layers`` are the enforcement points — every named layer's module
    must reject through :func:`check` (statically verified by
    ``repro.analysis``)."""

    key: str
    features: tuple[str, ...]
    layers: tuple[str, ...]
    reason: str


RULES: tuple[CompatRule, ...] = (
    # order matters: check() raises the FIRST active rule, so specific
    # program-shape rules come before the broad pairwise ones (mirrors the
    # historical raise order of the executor's constructor)
    CompatRule(
        key="merge-fn-nowait",
        features=("merge_fn", "nowait"),
        layers=("executor",),
        reason=(
            "a program merge_fn (non-uniform cuts) cannot EMA-impute "
            "missing clients — there is no per-client frame to impute "
            "into the concatenation; use a barrier runtime "
            "(serial/pipelined)"),
    ),
    CompatRule(
        key="secure-nonadditive",
        features=("secure", "nonadditive"),
        layers=("config", "executor"),
        reason=(
            "secure aggregation needs an additively homomorphic merge "
            "(sum/avg) for the pairwise masks to cancel — max/mul/concat "
            "have no mask-cancelling sum"),
    ),
    CompatRule(
        key="secure-merge-fn",
        features=("secure", "merge_fn"),
        layers=("executor", "train"),
        reason=(
            "secure aggregation cannot run a program merge_fn "
            "(non-uniform cuts, e.g. the vlm sequence concat): role 0 "
            "must SUM the masked cuts for the pairwise masks to cancel, "
            "and a concatenation exposes each masked segment with nothing "
            "to cancel against"),
    ),
    CompatRule(
        key="secure-nowait",
        features=("secure", "nowait"),
        layers=("executor", "train", "launch"),
        reason=(
            "secure aggregation requires barrier execution: a client "
            "dropped in no-wait mode leaves its pairwise masks "
            "uncancelled and the aggregate unusable — there is no "
            "dropout-recovery round"),
    ),
    CompatRule(
        key="secure-compress",
        features=("compress", "secure"),
        layers=("schedule", "engine", "executor", "worker", "train",
                "launch"),
        reason=(
            "secure aggregation and cut compression cannot compose: "
            "additive masks do not cancel through quantized/sparsified "
            "values, so the merged sum would be garbage while the uplinks "
            "silently stop being blinded aggregates — run one or the "
            "other"),
    ),
    CompatRule(
        key="compress-merge-fn",
        features=("compress", "merge_fn"),
        layers=("executor",),
        reason=(
            "cut compression cannot run under a program merge_fn "
            "(non-uniform cuts, e.g. the vlm sequence concat): the wire "
            "contract audits one k-per-vector frame per uplink, which a "
            "non-uniform concatenation does not have"),
    ),
    CompatRule(
        key="tree-nonadditive",
        features=("tree", "nonadditive"),
        layers=("engine", "executor", "train", "launch"),
        reason=(
            "tree aggregation needs an additively homomorphic merge: "
            "relays forward SUBTREE PARTIAL SUMS, which only a plain "
            "additive merge (sum/avg) regroups — max/mul/concat have no "
            "partial-sum regrouping"),
    ),
    CompatRule(
        key="tree-merge-fn",
        features=("tree", "merge_fn"),
        layers=("executor", "train"),
        reason=(
            "tree aggregation cannot run a program merge_fn (non-uniform "
            "cuts, e.g. the vlm sequence concat): relays partial-sum "
            "uniform cut tensors under an additive merge (sum/avg), and a "
            "concatenation has no subtree partial sum"),
    ),
    CompatRule(
        key="tree-compress",
        features=("tree", "compress"),
        layers=("schedule", "engine", "executor", "worker", "train",
                "launch"),
        reason=(
            "tree aggregation and cut compression cannot compose: relays "
            "partial-sum cut tensors, and codec frames (topk bitmaps / "
            "int8 codes) cannot be partial-summed without breaking each "
            "stream's error-feedback state — run one or the other"),
    ),
    CompatRule(
        key="tree-nowait",
        features=("tree", "nowait"),
        layers=("engine", "executor", "train", "launch"),
        reason=(
            "tree aggregation requires barrier execution: a client "
            "folded into a relay's combined frame has no per-client "
            "arrival to deadline, drop, or EMA-impute at a no-wait "
            "merge"),
    ),
    CompatRule(
        key="serve-secure",
        features=("serve", "secure"),
        layers=("schedule", "serve", "worker"),
        reason=(
            "split serving ships raw cut frames: secure aggregation's "
            "masked uplinks are a training-path feature and do not "
            "compose with the serving schedule"),
    ),
    CompatRule(
        key="serve-compress",
        features=("serve", "compress"),
        layers=("schedule", "serve", "worker"),
        reason=(
            "split serving ships raw cut frames: cut compression is a "
            "training-path feature and does not compose with the serving "
            "schedule"),
    ),
    CompatRule(
        key="serve-tree",
        features=("serve", "tree"),
        layers=("schedule",),
        reason=(
            "split serving ships raw cut frames: the aggregation tree is "
            "a training-path overlay with no serving schedule"),
    ),
)

RULES_BY_KEY = {rule.key: rule for rule in RULES}
LAYERS = tuple(LAYER_MODULES)


class CompatError(ValueError):
    """An unsound feature composition, rejected at ``layer`` by ``rule``."""

    def __init__(self, rule: CompatRule, layer: str, context: str = ""):
        self.rule = rule
        self.layer = layer
        self.context = context
        prefix = f"{context}: " if context else ""
        super().__init__(f"{prefix}{rule.reason}")


def active_features(*, secure=False, compress=None, tree=None, nowait=False,
                    merge_fn=None, merge=None,
                    serve=False) -> dict[str, bool]:
    """Normalize heterogeneous caller flags (an AggTree object, a codec
    scheme string, a merge name, a callable) into the boolean feature set
    the rules are written over."""
    return {
        "secure": bool(secure),
        "compress": compress is not None and compress is not False,
        "tree": tree is not None and tree is not False,
        "nowait": bool(nowait),
        "merge_fn": merge_fn is not None and merge_fn is not False,
        "nonadditive": merge is not None and merge not in ADDITIVE_MERGES,
        "serve": bool(serve),
    }


def check(layer: str, *, secure=False, compress=None, tree=None,
          nowait=False, merge_fn=None, merge=None,
          serve=False, context: str = "") -> None:
    """Reject the first matrix rule whose features are all active and
    which declares ``layer`` as an enforcement point.

    A flag a caller does not pass defaults to inactive — the static
    analyzer (rule C001) verifies every declared layer passes every
    feature flag its rules need, so a layer cannot opt out by omission.
    """
    if layer not in LAYER_MODULES:
        raise ValueError(f"unknown compat layer {layer!r} "
                         f"(declared: {LAYERS})")
    active = active_features(
        secure=secure, compress=compress, tree=tree, nowait=nowait,
        merge_fn=merge_fn, merge=merge, serve=serve)
    for rule in RULES:
        if layer in rule.layers and all(active[f] for f in rule.features):
            raise CompatError(rule, layer, context)


def cli_reject(e: CompatError) -> "SystemExit":
    """The launcher's phrasing of a matrix rejection: name the flags, then
    the matrix reason — '--compress cannot run with --secure-agg: ...'."""
    a, b = (CLI_NAMES[f] for f in e.rule.features[:2])
    return SystemExit(f"{a} cannot run with {b}: {e.rule.reason}")


def render_markdown() -> str:
    """The rejection matrix as a markdown table — the committed copy at
    ``docs/compat_matrix.md`` is verified against this exact rendering by
    ``repro.analysis`` (rule D001)."""
    lines = [
        "# Feature-interaction compatibility matrix",
        "",
        "Generated from `repro.core.compat.RULES` — do not edit by hand;",
        "regenerate with:",
        "",
        "```",
        "PYTHONPATH=src python -c \\",
        "  'from repro.core import compat; print(compat.render_markdown(),"
        " end=\"\")' \\",
        "  > docs/compat_matrix.md",
        "```",
        "",
        "Every layer listed for a rule rejects the composition through",
        "`compat.check`; `python -m repro.analysis` statically verifies",
        "each layer's module passes the rule's feature flags.",
        "",
        "| rule | features | enforced at | why |",
        "|---|---|---|---|",
    ]
    for rule in RULES:
        lines.append(
            f"| `{rule.key}` | {' x '.join(rule.features)} | "
            f"{', '.join(rule.layers)} | {rule.reason} |")
    return "\n".join(lines) + "\n"
