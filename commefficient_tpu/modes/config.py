"""Static configuration for compression modes + server optimizer semantics.

Mirrors the reference's `argparse` surface (SURVEY.md §5.6: --mode,
--error_type, --local_momentum/--virtual_momentum, --k, --num_rows,
--num_cols, --num_blocks, --num_local_iters, ...) as one frozen, hashable
dataclass that jitted round steps can close over.
"""

from __future__ import annotations

import dataclasses

MODES = ("sketch", "true_topk", "local_topk", "fedavg", "localSGD", "uncompressed")


@dataclasses.dataclass(frozen=True)
class ModeConfig:
    mode: str
    d: int  # flat gradient dimensionality
    k: int = 0  # top-k size (sketch / true_topk / local_topk)
    num_rows: int = 5  # sketch rows r
    num_cols: int = 0  # sketch cols c
    num_blocks: int = 1
    seed: int = 42
    momentum: float = 0.9
    momentum_type: str = "virtual"  # none | virtual | local
    error_type: str = "virtual"  # none | virtual | local
    num_local_iters: int = 1  # fedavg / localSGD local steps
    server_lr: float = 1.0  # weight-delta modes only: scales the averaged
    # delta at the server ("slowmo" server optimizer — with momentum_type=
    # "virtual" the server runs momentum-SGD over round deltas; SURVEY.md §3.1
    # "fedavg: server LR / slowmo optional")
    num_clients: int = 0  # total virtual clients (for local state allocation)
    hash_family: str = "rotation"  # sketch bucket-hash family (see CSVecSpec);
    # "rotation" is the TPU-fast default, "random" the reference-like one
    topk_impl: str = "exact"  # server/client top-k selection: "exact"
    # (lax.top_k's result; from csvec.TOPK_SELECT_MIN_N elements on by a
    # counted threshold and a compaction, csvec.select_topk_abs, not by
    # the full sort the TPU lowers lax.top_k to), "approx"
    # (lax.approx_max_k, TPU PartialReduce lowering at topk_recall, then
    # the exact k largest of the partial maxima, by csvec's selection
    # where there are enough of them and by approx_max_k's own sort below
    # that; exact elsewhere), or "oversample" (approx preselect of 4k
    # candidates + exact refine — near-exact at PartialReduce speed;
    # csvec.topk_abs).
    # Accuracy impact of approx: the paper-scale 2x2 seed
    # replication put exact-vs-approx@0.99 within seed variance
    # (single-seed orderings inverted across seeds — results/README.md),
    # so any recall cost is below that study's resolution; "oversample"
    # makes the question moot by construction.
    topk_recall: float = 0.95  # approx_max_k recall_target for
    # topk_impl="approx" and for oversample's preselect pass.
    server_state: str = "dense"  # representation of the SERVER optimizer
    # state (Vvelocity/Verror): "dense" keeps the [d] vectors (the seed
    # behavior, bit-for-bit); "sketch" keeps them as r x c Count-Sketch
    # tables updated by table arithmetic (arXiv:1902.00179 — momentum and
    # error feedback in sketch space), with `unsketch_topk` unchanged
    # downstream, so server memory stops scaling with d: O(r*c) replaces
    # O(2d). Scope: the top-k-release modes (true_topk; local_topk with
    # error_type virtual) — mode=sketch already IS sketch-state
    # (FetchSGD Alg. 1), both values are accepted there and mean the same
    # thing. The client wire stays what the mode says it is (dense for
    # true_topk/local_topk), so the DP noise hook keeps its calibrated
    # dense-wire sensitivity; the server sketches AFTER aggregation/noise.
    # Exactness: with c >= d (and the rotation family) every row is a
    # signed permutation — collisions are impossible, estimates are exact,
    # and sketch-state is BIT-identical to dense-state (pinned in
    # tests/test_layerwise.py); with c < d it is the FetchSGD-style
    # approximation (heavy hitters survive, small coordinates blur).
    agg_op: str = "mean"  # how client wires combine: "mean" | "sum".
    # FetchSGD Alg. 1 writes the round sketch as a sum over client sketches
    # (SURVEY.md §3.1) with the scaling absorbed into the learning rate; this
    # library defaults to the mean (an unbiased gradient estimate independent
    # of cohort size). The two are EXACTLY equivalent for every mode here:
    # agg_op="sum" at lr η reproduces agg_op="mean" at lr η·W bit-for-bit
    # (server steps are positively homogeneous: top-k selection is
    # scale-invariant, everything else linear — tested in
    # tests/test_modes.py::test_sum_vs_mean_lr_translation). When reproducing
    # reference CLI hyperparameters (e.g. lr_scale 0.4), use agg_op="sum".
    # Weight-delta modes (fedavg/localSGD) reject "sum": their lr is consumed
    # inside the nonlinear local-SGD loop and the server applies the
    # aggregate at unit rate, so no lr knob can absorb the factor W — a sum
    # of W deltas would just be a W-times-too-large step.

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"unknown mode {self.mode!r}; expected one of {MODES}")
        if self.mode in ("sketch",) and (self.num_cols <= 0 or self.k <= 0):
            raise ValueError("mode=sketch requires num_cols > 0 and k > 0")
        if self.mode in ("true_topk", "local_topk") and self.k <= 0:
            raise ValueError(f"mode={self.mode} requires k > 0")
        if self.topk_impl not in ("exact", "approx", "oversample"):
            raise ValueError(f"bad topk_impl {self.topk_impl!r}")
        if not (0.0 < self.topk_recall <= 1.0):
            raise ValueError(f"topk_recall must be in (0, 1], got "
                             f"{self.topk_recall}")
        if self.momentum_type not in ("none", "virtual", "local"):
            raise ValueError(f"bad momentum_type {self.momentum_type!r}")
        if self.error_type not in ("none", "virtual", "local"):
            raise ValueError(f"bad error_type {self.error_type!r}")
        if self.agg_op not in ("mean", "sum"):
            raise ValueError(f"bad agg_op {self.agg_op!r}; expected 'mean' or 'sum'")
        if self.server_state not in ("dense", "sketch"):
            raise ValueError(
                f"bad server_state {self.server_state!r}; expected 'dense' "
                "or 'sketch'")
        if self.server_state == "sketch" and self.mode != "sketch":
            if self.mode not in ("true_topk", "local_topk"):
                raise ValueError(
                    f"server_state='sketch' needs a top-k release to stay in "
                    f"sketch space; mode={self.mode!r} releases a dense delta "
                    "(querying every coordinate back out would materialize "
                    "[d] and defeat the O(r*c) state)"
                )
            if self.mode == "local_topk" and self.error_type != "virtual":
                raise ValueError(
                    "server_state='sketch' with mode='local_topk' requires "
                    "error_type='virtual': only the virtual-error branch "
                    "releases a top-k (the others release lr*V densely, "
                    "which a sketch-resident V cannot produce without "
                    "querying every coordinate back out)"
                )
            if self.num_cols <= 0:
                raise ValueError(
                    "server_state='sketch' requires num_cols > 0 (the "
                    "r x c table shape comes from num_rows/num_cols)"
                )
        if self.server_lr != 1.0 and self.mode not in ("fedavg", "localSGD"):
            raise ValueError(
                "server_lr applies only to weight-delta modes (fedavg/localSGD); "
                "grad modes take their server rate from the lr schedule"
            )
        if self.agg_op == "sum" and self.mode in ("fedavg", "localSGD"):
            raise ValueError(
                f"mode={self.mode} requires agg_op='mean': the server applies the "
                "aggregated weight delta at unit rate, so summing W deltas is a "
                "W-times-too-large step with no lr knob to absorb it"
            )
        # Reject combinations the mode library does not implement, rather than
        # silently running a different algorithm than the user configured.
        allowed = {
            "sketch": {"momentum": ("none", "virtual"), "error": ("virtual",)},
            "true_topk": {"momentum": ("none", "virtual"), "error": ("none", "virtual")},
            "local_topk": {"momentum": ("none", "virtual", "local"), "error": ("none", "local", "virtual")},
            "fedavg": {"momentum": ("none", "virtual", "local"), "error": ("none",)},
            "localSGD": {"momentum": ("none", "virtual", "local"), "error": ("none",)},
            "uncompressed": {"momentum": ("none", "virtual"), "error": ("none",)},
        }[self.mode]
        if self.momentum_type not in allowed["momentum"]:
            raise ValueError(
                f"mode={self.mode} supports momentum_type {allowed['momentum']}, "
                f"got {self.momentum_type!r}"
            )
        if self.error_type not in allowed["error"]:
            raise ValueError(
                f"mode={self.mode} supports error_type {allowed['error']}, "
                f"got {self.error_type!r}"
            )

    @property
    def sketch_spec(self):
        from ..sketch import CSVecSpec

        return CSVecSpec(
            d=self.d, c=self.num_cols, r=self.num_rows, num_blocks=self.num_blocks,
            seed=self.seed, family=self.hash_family,
        )

    @property
    def uses_weight_delta(self) -> bool:
        """fedavg/localSGD clients send weight deltas from >1 local steps; all
        other modes send (transforms of) a single gradient."""
        return self.mode in ("fedavg", "localSGD")

    @property
    def needs_local_state(self) -> bool:
        """Per-client persistent state ([num_clients, d] — the memory wall,
        SURVEY.md §3.3) is only needed for client-side momentum/error."""
        return self.mode == "local_topk" and (
            self.momentum_type == "local" or self.error_type == "local"
        )
