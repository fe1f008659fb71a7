"""The flag surface — reference CLI compatibility (SURVEY.md §5.6).

One argparse namespace drives everything, as in the reference's
`utils.parse_args`. Flag names follow the reference ([K]-provenance; SURVEY.md
notes they may differ from the mounted fork — re-ground via SURVEY.md §0.3
when the mount is populated).
"""

from __future__ import annotations

import argparse

from ..modes.config import MODES, ModeConfig


def make_parser(task: str = "cv") -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=f"commefficient-tpu {task} training")
    # compression / update mode
    p.add_argument("--mode", default="uncompressed", choices=list(MODES))
    p.add_argument("--error_type", default=None, choices=["none", "local", "virtual"],
                   help="default: virtual for sketch/true_topk, local for local_topk, else none")
    p.add_argument("--momentum_type", default=None, choices=["none", "virtual", "local"],
                   help="default: virtual when --momentum > 0 (local for local_topk), else none")
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--k", type=int, default=50000)
    p.add_argument("--num_rows", type=int, default=5)
    p.add_argument("--num_cols", type=int, default=500000)
    p.add_argument("--num_blocks", type=int, default=1)
    p.add_argument("--hash_family", default="rotation", choices=["rotation", "random"],
                   help="sketch bucket-hash family: rotation = TPU-fast roll-based "
                        "(default), random = reference-like per-coordinate hashing")
    p.add_argument("--topk_impl", default="exact",
                   choices=["exact", "approx", "oversample"],
                   help="top-k selection: exact (lax.top_k's result, by "
                        "threshold and compaction where d is large), approx "
                        "(lax.approx_max_k's partial maxima at "
                        "--topk_recall, then the exact k largest of them, "
                        "by selection where they are many; TPU-fast; "
                        "paper-scale accuracy impact within seed variance "
                        "at recall 0.99 — results/README.md), or oversample "
                        "(approx 4k-candidate preselect + exact refine: "
                        "near-exact at approx speed by construction)")
    p.add_argument("--topk_recall", type=float, default=0.95,
                   help="approx_max_k recall_target for --topk_impl approx "
                        "and for oversample's preselect pass")
    p.add_argument("--sketch_path", default="ravel",
                   choices=["ravel", "layerwise"],
                   help="mode=sketch only: how the round's Count-Sketch "
                        "table is built. ravel (default) concatenates every "
                        "layer into one flat [d] gradient before "
                        "compressing (the reference flat path); layerwise "
                        "folds "
                        "each layer's gradient block straight into the "
                        "running r x c table as it comes off the backward "
                        "pass — the dense [d] gradient (and the flat "
                        "params copy for the delta apply) never "
                        "materializes, so peak sketch-side memory is "
                        "O(r*c) + one layer instead of O(d). Pinned "
                        "bit-identical to ravel (fused, split, sharded)")
    p.add_argument("--server_state", default="dense",
                   choices=["dense", "sketch"],
                   help="server optimizer state representation: dense "
                        "(default; [d] Vvelocity/Verror, the seed "
                        "behavior) or sketch (momentum + virtual error "
                        "feedback kept as r x c Count-Sketch tables — "
                        "server memory stops scaling with d; true_topk "
                        "and local_topk-with-virtual-error only; "
                        "mode=sketch is already sketch-state and accepts "
                        "both). With --num_cols >= d the sketch is a "
                        "lossless signed permutation and matches dense "
                        "bit-for-bit; below that it is the FetchSGD-style "
                        "approximation")
    p.add_argument("--agg_op", default="mean", choices=["mean", "sum"],
                   help="client-wire aggregation: mean (cohort-size-independent "
                        "default) or sum (FetchSGD Alg. 1 semantics — use with "
                        "reference lr_scale values; sum@lr == mean@lr*W exactly)")
    # federation shape
    p.add_argument("--num_clients", type=int, default=100)
    p.add_argument("--num_workers", type=int, default=8,
                   help="clients sampled (simulated) per round")
    p.add_argument("--local_batch_size", type=int, default=8)
    p.add_argument("--num_local_iters", type=int, default=1)
    p.add_argument("--server_lr", type=float, default=1.0,
                   help="fedavg/localSGD: server rate on the averaged weight "
                        "delta (with --momentum_type virtual this is slowmo)")
    p.add_argument("--iid", action="store_true")
    # optimisation
    p.add_argument("--num_epochs", type=float, default=24)
    p.add_argument("--lr_scale", type=float, default=0.4)
    p.add_argument("--pivot_epoch", type=float, default=5)
    p.add_argument("--weight_decay", type=float, default=5e-4)
    # differential privacy (upstream fork deltas — SURVEY.md §0.5)
    p.add_argument("--dp_clip", type=float, default=0.0,
                   help="L2 clip per client update (0 = off)")
    p.add_argument("--dp_noise", type=float, default=0.0,
                   help="central-DP noise multiplier on the aggregate (needs --dp_clip)")
    # run plumbing
    p.add_argument("--client_dropout", type=float, default=0.0,
                   help="per-round probability each sampled client drops "
                        "before aggregation (straggler simulation; the "
                        "reference has none — a dead worker hangs it)")
    p.add_argument("--client_update_clip", type=float, default=0.0,
                   help="sketch-space quarantine: reject any client whose "
                        "update L2 exceeds this multiple of the running "
                        "median of live client norms (non-finite updates "
                        "always rejected) — the client is zeroed out of the "
                        "merge and removed from the renormalization, so one "
                        "poisoned update costs one client, not the round. "
                        "Counted per round as clients_quarantined. 0 = off")
    p.add_argument("--merge_policy", default="sum",
                   choices=["sum", "trimmed", "median"],
                   help="how per-client Count-Sketch tables combine into "
                        "the round aggregate. sum (pinned default): the "
                        "linear ordered sum — FetchSGD's merge, maximally "
                        "accurate and exactly what a Byzantine minority "
                        "exploits. trimmed: per table coordinate, drop the "
                        "--merge_trim highest and lowest live "
                        "contributions before the ordered sum (trimmed "
                        "mean; deterministic tie-break by client index, "
                        "mesh-shape-invariant; trim=0 is BIT-identical to "
                        "sum by construction). median: coordinate-wise "
                        "median. Robust policies need per-client tables, "
                        "so they forfeit the compress-once linearity "
                        "shortcut (the round runs the wire-payload shape "
                        "even unserved) and require --mode sketch with "
                        "--sketch_path ravel; they also weaken error-"
                        "feedback exactness (see README threat model)")
    p.add_argument("--merge_trim", type=int, default=0,
                   help="--merge_policy trimmed: contributions dropped per "
                        "coordinate from EACH end (defends up to this many "
                        "colluders; needs 2*trim < --num_workers). 0 = "
                        "trim nothing = the sum program, bit-identically")
    p.add_argument("--robust_residual", default="off",
                   choices=["off", "on"],
                   help="error-feedback-aware robust merges (--merge_policy "
                        "trimmed|median): accumulate the robust-vs-mean "
                        "merge residual into the Verror table, with the "
                        "mean WINSORIZED into the policy's kept window — "
                        "the honest mass the trim clips re-enters through "
                        "error feedback (telescoping survives the robust "
                        "merge) while an adversary's residual contribution "
                        "stays bounded by the clean value range. off "
                        "(default) keeps the PR 10 robust program "
                        "bit-for-bit; MIGRATION.md notes the intent to "
                        "flip after a soak")
    p.add_argument("--quarantine_scope", default="cohort",
                   choices=["cohort", "layer"],
                   help="--client_update_clip screen granularity. cohort "
                        "(default): one L2 norm per client vs the running "
                        "cohort median (the original screen, unchanged). "
                        "layer: ADDITIONALLY screen each client's update "
                        "per LAYER — per-leaf L2 vs that leaf's own "
                        "running median ring (--quarantine_window applies "
                        "per leaf), a client over ANY leaf's screen is "
                        "dropped — so an attack hiding inside the flat "
                        "norm (all its mass in one layer) still trips. "
                        "Single-leaf models are bit-identical to cohort "
                        "scope on the update-norm (announce) rounds; "
                        "table rounds (--serve_payload sketch / robust "
                        "--merge_policy) add the update-space per-leaf "
                        "screen beside the table-space one even "
                        "single-leaf. Fused round paths only (widens the "
                        "quarantine state tree — see MIGRATION.md)")
    p.add_argument("--quarantine_window", type=int, default=1,
                   help="--client_update_clip threshold baseline: 1 "
                        "(default) screens against the LAST non-empty "
                        "round's live-cohort median (the pre-window "
                        "behavior, bit-identical); K > 1 screens against "
                        "the median over a ring of the last K rounds' "
                        "medians, so models whose update norms drift fast "
                        "don't quarantine healthy clients (one outlier "
                        "round perturbs one window slot, not the whole "
                        "threshold)")
    p.add_argument("--requeue_policy", default="fifo",
                   choices=["fifo", "aged"],
                   help="serving order for the dropped-client re-queue: "
                        "fifo (default; substitution order = drop order) or "
                        "aged (weighted choice by rounds-waiting from a "
                        "pinned dedicated seed — at high drop rates FIFO "
                        "can starve recently-dropped clients behind a long "
                        "head; aged keeps expected wait bounded). Both "
                        "consume zero host-sampling RNG, so the sampled "
                        "cohort stream is policy-invariant")
    # streaming aggregation service (serve/): clients PUSH submissions at a
    # continuously-running aggregator instead of the loop pulling them
    p.add_argument("--serve", default="off",
                   choices=["off", "inproc", "socket"],
                   help="run as a streaming aggregation service: cohorts "
                        "assemble from a PUSH arrival stream (trace-driven "
                        "traffic generator) with W-of-N round close, "
                        "admission control, and backpressure, instead of "
                        "the loop sampling clients itself. inproc = "
                        "in-process submissions (deterministic; the parity "
                        "path), socket = loopback-TCP JSON-lines wire. "
                        "off (default) = the batch simulator")
    p.add_argument("--serve_quorum", type=int, default=0,
                   help="W of the W-of-N round close: the round closes as "
                        "soon as this many of the --num_workers invited "
                        "clients have submitted; stragglers and no-shows "
                        "are masked + re-queued (bit-identical to the "
                        "round over the survivors). 0 = full cohort")
    p.add_argument("--serve_deadline", type=float, default=4.0,
                   help="round-close deadline in (virtual) seconds: a "
                        "round short of quorum closes degraded here")
    p.add_argument("--serve_trace", default="",
                   help="traffic-generator trace spec, 'k=v,...' over "
                        "population/base_rate/diurnal_amplitude/"
                        "diurnal_period_s/burst_rate/burst_size/seed "
                        "(serve.TraceConfig); unset = defaults with "
                        "population=num_clients and seed=--seed")
    p.add_argument("--serve_payload", default="announce",
                   choices=["announce", "sketch"],
                   help="what a submission carries. announce (default): an "
                        "arrival announcement — the engine computes every "
                        "update server-side from the client's shard. "
                        "sketch: the client's REAL r x c Count-Sketch table "
                        "crosses the wire (length-prefixed, checksummed, "
                        "schema-versioned frames on the socket transport), "
                        "runs the server's validation gauntlet "
                        "(MALFORMED/STALE_SCHEMA/QUARANTINED rejections), "
                        "and the server merely SUMS accepted tables — the "
                        "linearity FetchSGD is servable on. Requires "
                        "--mode sketch; announce stays the default until "
                        "the payload path soaks (see MIGRATION.md)")
    p.add_argument("--serve_shed_watermark", type=float, default=0.0,
                   help="load shedding: reject submissions with SHEDDING "
                        "(+ a retry-after hint on the socket wire) once "
                        "queue depth passes this fraction of total "
                        "capacity, BEFORE any per-submission work — "
                        "overload degrades gracefully instead of queuing "
                        "unboundedly. 0 = off (hard QUEUE_FULL only)")
    p.add_argument("--serve_pipeline", action="store_true",
                   help="always-on aggregation: run the serve cycle "
                        "(invite -> collect -> close -> prep) on a "
                        "double-buffered worker AHEAD of the merge, so "
                        "round r+1's ingest overlaps round r's merge and "
                        "the commit-to-dispatch gap collapses "
                        "(server_idle_ms ~ 0). Bit-identical to the serial "
                        "served loop by construction (same producer order, "
                        "dispatch-gated payload compute)")
    p.add_argument("--serve_async", action="store_true",
                   help="buffered ASYNCHRONOUS aggregation (FedBuff-"
                        "shaped): rounds close at a buffer-size trigger "
                        "(--serve_buffer) instead of the W-of-N quorum, "
                        "and late tables — stragglers past the trigger, "
                        "pushes for a recently-closed round — fold into a "
                        "later merge weighted (1+lag)^-alpha instead of "
                        "being discarded. Requires --serve_payload sketch. "
                        "Composes with --merge_policy trimmed|median: the "
                        "per-BUFFER robust merge runs the order statistics "
                        "over {current buffer + staleness-weighted stale "
                        "folds}, so a stale adversarial table is trimmed "
                        "like an on-time one. Sync stays the parity "
                        "reference: an async run where everyone answers on "
                        "time is pinned bitwise == the sync run (zero-"
                        "stale robust rounds == the sync robust program)")
    p.add_argument("--serve_buffer", type=int, default=0,
                   help="--serve_async: merged-table count that triggers a "
                        "round's merge (replaces the quorum; 0 = the "
                        "--serve_quorum value)")
    p.add_argument("--serve_staleness", type=float, default=0.5,
                   help="--serve_async: staleness exponent alpha — a table "
                        "lag rounds late folds with weight (1+lag)^-alpha "
                        "(0 = unweighted, FedBuff default 0.5)")
    p.add_argument("--serve_stale_rounds", type=int, default=1,
                   help="--serve_async: how many rounds behind the newest "
                        "window a late table is still admitted and folded; "
                        "older submissions bounce OUT_OF_ROUND and the "
                        "parked entry is dropped (counted)")
    p.add_argument("--serve_transport", default="eventloop",
                   choices=["threaded", "eventloop"],
                   help="--serve socket: the connection engine. eventloop "
                        "(default since PR 18): the serve/scale selectors "
                        "reactor — ONE thread multiplexing thousands of "
                        "connections (non-blocking accept, incremental "
                        "frame reassembly, read deadlines). The C1M path. "
                        "threaded (the reference, and the default before "
                        "PR 18): one OS thread per connection, capped — "
                        "fine for chaos tests, dead at heavy traffic; "
                        "pinning it prints a startup NOTE. Identical "
                        "admission decisions either way (shared protocol, "
                        "same G011 gauntlet).")
    p.add_argument("--serve_shards", type=int, default=0,
                   help=">= 2 shards the socket ingest that many ways, "
                        "clients routed by client-id hash — spreads "
                        "connection handling and payload-gauntlet CPU "
                        "across workers (reactor threads or real worker "
                        "processes; --serve_shard_mode). Per-shard "
                        "admission/shed counters and load-scaled retry-"
                        "after hints land in /metrics and /metrics.prom, "
                        "so an overloaded shard is distinguishable from "
                        "an overloaded server. Requires --serve socket "
                        "--serve_transport eventloop. 0 = one listener")
    p.add_argument("--serve_shard_mode", default="thread",
                   choices=["thread", "process"],
                   help="--serve_shards >= 2: what a shard IS. thread "
                        "(default): N reactor threads over the ONE "
                        "admission queue — connection scale-out, but "
                        "decode/gauntlet/admission still serialize on "
                        "this process's GIL. process: N SO_REUSEPORT "
                        "worker PROCESSES (serve/scale/procshard.py), "
                        "shared-nothing — each owns its clients' "
                        "admission state outright (dedup, pending, "
                        "quarantine screen against the round's broadcast "
                        "median) and lands validated tables in a shared-"
                        "memory ring block the root's close reads "
                        "directly; misroutes forward to the owner "
                        "(counted). A killed worker == its clients "
                        "dropped + re-queued bitwise (shard_kill fault "
                        "kind); dead workers respawn at the next round. "
                        "Served params stay BITWISE identical to thread "
                        "mode and to the unsharded path, fastpath on or "
                        "off. Does not compose with --serve_pipeline/"
                        "--serve_async/--serve_edges yet")
    p.add_argument("--serve_edges", type=int, default=0,
                   help=">= 2 arms TWO-TIER edge aggregation "
                        "(serve/scale/edge.py): the cohort partitions "
                        "over this many edge aggregators by client-id "
                        "hash; each edge validates + ordered-sums its "
                        "shard's tables and forwards ONE r x c partial "
                        "to the root (sketch linearity makes the tree "
                        "merge exact), which folds partials in fixed "
                        "edge order — pinned BITWISE equal to the flat "
                        "merge of the same edge-armed session over the "
                        "same surviving cohort. An edge dying == its "
                        "shard dropped + re-queued, bitwise (edge_kill "
                        "fault kind). Robust --merge_policy forces per-"
                        "client FORWARDING through the tree (loud note; "
                        "order statistics need individual tables). "
                        "Requires --serve_payload sketch; does not "
                        "compose with --serve_async/--serve_pipeline "
                        "yet. 0 = flat merge (the exact prior program)")
    p.add_argument("--serve_fastpath", action="store_true",
                   help="zero-copy ingest-to-merge fast path: accepted "
                        "r x c tables decode ONCE straight into a pinned "
                        "host ring block sized by the cohort (serve/"
                        "ring.py) and upload to device in chunks WHILE "
                        "the round window is still open; socket "
                        "transports also batch the validation gauntlet "
                        "over blocks of arrivals (vectorized finite/L2 "
                        "screening, --serve_gauntlet_workers). Per-"
                        "submission admission verdicts, their counters, "
                        "and the served round's bytes are pinned "
                        "BITWISE identical to the slow path — the ring "
                        "changes layout and copy count, never order. "
                        "Requires --serve_payload sketch; does not "
                        "compose with --serve_edges yet")
    p.add_argument("--serve_gauntlet_workers", type=int, default=2,
                   help="--serve_fastpath + --serve socket: worker "
                        "threads draining the batched validation "
                        "gauntlet (each drains up to 32 queued frames "
                        "per wake and screens them as one numpy block). "
                        "Inproc serving validates inline and ignores "
                        "this")
    p.add_argument("--serve_max_conns", type=int, default=0,
                   help="--serve socket: concurrent-connection cap of the "
                        "connection engine (per reactor when sharded) — "
                        "past it connections are refused and counted "
                        "(serve_conn_refused_total), never queued. 0 = "
                        "the engine default: threaded 128 (every "
                        "connection is an OS thread), eventloop 8192 "
                        "(fd-bounded)")
    p.add_argument("--serve_port", type=int, default=0,
                   help="--serve socket: loopback bind port (0 = ephemeral; "
                        "sharded ingest binds port+k per shard when set)")
    p.add_argument("--serve_metrics_port", type=int, default=-1,
                   help=">= 0 serves GET /metrics (JSON: round, queue "
                        "depth, arrival rate, quarantine/requeue counters) "
                        "on this loopback port (0 = ephemeral, printed at "
                        "startup); -1 = no endpoint")
    p.add_argument("--rounds_per_dispatch", type=int, default=1,
                   help="> 1 compiles this many rounds into one program "
                        "(lax.scan) with a single host sync per block — "
                        "amortizes the host round-trip; stateless modes only "
                        "(others silently run per-round)")
    p.add_argument("--sync_loop", action="store_true",
                   help="run the fully synchronous round loop: inline batch "
                        "assembly, a blocking metrics sync per dispatch, and "
                        "blocking checkpoint writes. The default ASYNC "
                        "harness (runner/) overlaps all three with device "
                        "compute and is pinned bit-identical to this loop; "
                        "--sync_loop is the escape hatch / A-B baseline")
    p.add_argument("--client_chunk", type=int, default=0,
                   help="> 0 scans the per-client grads in chunks of this "
                        "many clients (must divide --num_workers), so at "
                        "most client_chunk full gradients coexist in HBM — "
                        "lets GPT-2-scale rounds sample big cohorts per chip")
    # stub (removed in PR 29): benchmark/builders/common.py reads the
    # attribute; resolve_defaults exits when it is set
    p.add_argument("--split_compile", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--multihost", action="store_true",
                   help="force jax.distributed.initialize() at startup "
                        "(auto-detected multi-host environments initialize "
                        "without this flag; see parallel/distributed.py)")
    p.add_argument("--coordinator_address", default=None,
                   help="host:port of process 0 for --multihost on clusters "
                        "without auto-detection (non-TPU)")
    p.add_argument("--num_processes", type=int, default=None,
                   help="total hosts for --multihost (with "
                        "--coordinator_address)")
    p.add_argument("--process_id", type=int, default=None,
                   help="this host's rank for --multihost (with "
                        "--coordinator_address)")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--num_devices", type=int, default=0, help="0 = all visible")
    p.add_argument("--mesh", default="",
                   help="device mesh for the data-parallel federated round: "
                        "clients=N[,slices=M]. The sampled cohort shards "
                        "N-ways (xM across pod slices over DCN); each device "
                        "accumulates its shard's partial Count Sketch and "
                        "the cross-device merge ships one r x c table per "
                        "round instead of the dense [d] gradient. Errors if "
                        "the host exposes fewer devices than the spec needs. "
                        "Unset = shard over all visible devices (the sharded "
                        "round is the default whenever > 1 device is "
                        "visible); combine with --model_parallel/"
                        "--seq_parallel on the gpt2 CLI")
    p.add_argument("--max_inflight", type=int, default=0,
                   help="async loop: drain when this many rounds are "
                        "dispatched-uncommitted. 0 = auto-tune from the "
                        "measured host<->device round-trip so the per-drain "
                        "sync stays ~10%% of the amortized work (a slow host "
                        "link gets a deep chain, a local chip a shallow one)")
    p.add_argument("--prefetch_depth", type=int, default=0,
                   help="async round-preparation lookahead; 0 = auto "
                        "(double buffering, deepened on high-RTT links)")
    # resilience (resilience/: fault injection + failure recovery)
    p.add_argument("--fault_plan", default="",
                   help="deterministic fault-injection plan: ';'-separated "
                        "kind[@round,...][:key=val,...] entries — kinds: "
                        "preempt (SIGTERM mid-round), stall:secs=S / "
                        "data_fail:times=N (data-loader), eval_stall:secs=S "
                        "(eval loader), nonfinite[:value="
                        "inf] (NaN/Inf gradient burst), ckpt_fail:times=N / "
                        "ckpt_corrupt / ckpt_partial (checkpoint IO), "
                        "dist_init:times=N (distributed bootstrap), "
                        "client_drop:clients=I+J / client_straggle:clients="
                        "I,secs=S / client_poison:clients=I,value=nan|inf|"
                        "big (cohort-level: mask/stall/poison individual "
                        "clients inside the round), host_preempt:host=K "
                        "(SIGTERM one simulated host; the cross-host "
                        "barrier carries it to all), client_signflip:"
                        "clients=I / client_scale:clients=I,factor=F / "
                        "client_collude:frac=P (Byzantine wire attacks on "
                        "the per-client sketch table — mode=sketch table "
                        "rounds; answered by --merge_policy and the "
                        "quarantine), seed=N. "
                        "Unset = zero injection, zero behavior change")
    p.add_argument("--on_nonfinite", default="skip",
                   choices=["off", "skip", "halt"],
                   help="NaN/Inf aggregate guard: skip treats the poisoned "
                        "round as fully-dropped (momentum/error state stay "
                        "clean; counted in metrics), halt additionally "
                        "checkpoints and exits, off restores the unguarded "
                        "seed behavior (poison propagates into the params)")
    p.add_argument("--max_retries", type=int, default=3,
                   help="bounded retries (exponential backoff + jitter) for "
                        "checkpoint IO, distributed init, and data loading")
    p.add_argument("--no_emergency_checkpoint", action="store_true",
                   help="disable the watchdog's MID-ROUND emergency "
                        "checkpoint and keep server-state buffer donation "
                        "(saves one full state copy in HBM — for runs that "
                        "barely fit). Scheduled --checkpoint_every saves and "
                        "the preemption checkpoint still work: both run at "
                        "round boundaries where donation is safe")
    p.add_argument("--watchdog_abort", action="store_true",
                   help="arm the RoundWatchdog's final escalation stage: "
                        "after warn -> stack dump -> emergency checkpoint, "
                        "abort the wedged process with the resumable exit "
                        "status so a supervisor relaunches with --resume "
                        "(needs --checkpoint_dir)")
    # reference-CLI compatibility no-ops (SURVEY.md §5.6): the reference's
    # process/queue machinery needs them; the TPU engine has no worker
    # processes to pin or ports to bind. Accepted so reference launch
    # commands run unmodified; a note is printed if set.
    p.add_argument("--share_ps_gpu", action="store_true",
                   help="accepted for reference-CLI compatibility; no-op "
                        "(no parameter-server process exists here)")
    p.add_argument("--port", type=int, default=0,
                   help="accepted for reference-CLI compatibility; no-op "
                        "(no torch.multiprocessing rendezvous here)")
    p.add_argument("--eval_batch_size", type=int, default=512)
    p.add_argument("--eval_every", type=int, default=0, help="rounds; 0 = once per epoch")
    p.add_argument("--num_rounds", type=int, default=0,
                   help="hard round cap (0 = derive from epochs); handy for smoke tests")
    p.add_argument("--data_root", default="./data")
    p.add_argument("--checkpoint_dir", default="")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--checkpoint_every", type=int, default=0, help="rounds; 0 = never")
    p.add_argument("--log_jsonl", default="")
    # observability (obs/): round tracing + metrics registry + profiler
    p.add_argument("--trace", default="",
                   help="write a Chrome-trace/Perfetto JSON of the run "
                        "here: host-side spans on named tracks (runner, "
                        "device, writer, serve-ingest, assembler, "
                        "federated, resilience) with deferred device-phase "
                        "durations resolved at drain boundaries — zero "
                        "host syncs added, traced run bit-identical to "
                        "untraced. Open in chrome://tracing or "
                        "ui.perfetto.dev")
    p.add_argument("--trace_events", default="",
                   help="append obs events as JSONL here (one schema-"
                        "versioned object per span/instant, line-buffered "
                        "whole-line writes — crash-safe); independent of "
                        "--trace, both may be set")
    p.add_argument("--profile_rounds", default="",
                   help="START:END — programmatic jax.profiler capture "
                        "window: start_trace before round START "
                        "dispatches, stop_trace after round END commits "
                        "(whole rounds, async pipeline included). Needs "
                        "--profile_dir; degrades to a loud no-op where "
                        "the profiler is unavailable. Without this flag "
                        "--profile_dir still captures the whole run")
    p.add_argument("--profile_dir", default="", help="write a jax.profiler trace here")
    p.add_argument("--health_every", type=int, default=0,
                   help="N > 0 computes sketch-health estimators ON DEVICE "
                        "inside the round program every N rounds (mode="
                        "sketch, fused/sharded/served paths): heavy-hitter "
                        "mass + top-k recall proxy, table saturation/"
                        "collision proxy, error-feedback Verror telescoping "
                        "health, per-leaf gradient-norm distribution, "
                        "uplink-vs-dense bytes — resolved at the existing "
                        "drain boundary (zero added host syncs) into "
                        "health_* registry gauges, /metrics, the trace, and "
                        "the round ledger. Estimators only READ round "
                        "state: a health-armed run is pinned bit-identical "
                        "to an unarmed one. 0 = off (the seed program, "
                        "bit-for-bit)")
    p.add_argument("--ledger", default="",
                   help="append one schema-versioned JSONL record per "
                        "COMMITTED round here (cohort + masks, admission/"
                        "quarantine/attack/stale-fold counter deltas, "
                        "health block, params/optimizer fingerprints) — "
                        "written with the whole-line crash-safe discipline, "
                        "riding the committed-snapshot rewind (uncommitted "
                        "rounds never appear; --resume continues the same "
                        "file gap-free). Also arms the crash postmortem "
                        "bundle at PATH.postmortem/ (trace + ledger tail + "
                        "registry snapshot + resolved config on watchdog "
                        "abort / unhandled exception / exit 75). Inspect "
                        "with `python -m commefficient_tpu.obs.ledger "
                        "diff|replay-check`")
    p.add_argument("--slo", default="off", choices=["off", "warn", "halt"],
                   help="arm the SLO/anomaly engine: windowed rules over "
                        "the committed round series (default set: "
                        "quarantine-rate spike, recall-proxy floor, stale-"
                        "fold runaway, server_idle_ms regression, non-"
                        "finite streak), evaluated at each commit. warn = "
                        "stderr + slo_* counters + trace instant; halt = "
                        "additionally checkpoint and exit cleanly at the "
                        "next drain boundary (the --on_nonfinite halt "
                        "discipline)")
    p.add_argument("--slo_rules", default="",
                   help="';'-separated rule specs overriding the default "
                        "set: name:series(>|<|^)threshold[@window] — e.g. "
                        "'q_spike:quarantine_rate>0.2@8;recall:"
                        "topk_mass_proxy<0.1@4'. > / < compare the "
                        "windowed mean; ^ fires when the current window "
                        "exceeds threshold x the older baseline "
                        "(regression). Series: any per-round metric, "
                        "quarantine_rate, stale_fraction, server_idle_ms, "
                        "or any health_* estimator name (needs "
                        "--health_every). Requires --slo")
    p.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"],
                   help="model compute dtype (params/BN/logits stay float32); "
                        "bfloat16 runs convs/matmuls on the TPU MXU at full rate")
    if task == "cv":
        p.add_argument("--dataset", default="cifar10",
                       choices=["cifar10", "cifar100", "femnist"])
        p.add_argument("--synthetic_separation", type=float, default=1.0,
                       help="class-prototype scale for the synthetic CIFAR "
                            "fallback: 1.0 = trivially separable (smoke "
                            "tests); ~0.025 puts Bayes accuracy near 0.86 "
                            "so accuracy-vs-comm trade-offs are meaningful")
        p.add_argument("--synthetic_train", type=int, default=10000,
                       help="synthetic-CIFAR fallback train-set size; 50000 "
                            "matches real CIFAR so paper-scale cohorts "
                            "(10,000 sort-by-label clients) get the same 5 "
                            "images/client as BASELINE config #2")
    else:  # gpt2
        p.add_argument("--dataset", default="personachat", choices=["personachat"])
        p.add_argument("--seq_len", type=int, default=256)
        p.add_argument("--model_size", default="small", choices=["tiny", "small"])
        p.add_argument("--model_config", default="",
                       help="a configuration file (JSON) whose `model` block "
                            "names another architecture to train in GPT-2's "
                            "place, by its model_type: qwen3_next (Gated "
                            "DeltaNet + gated attention + top-k experts, "
                            "models/qwen3_next.py) or glm4_moe_lite (latent "
                            "attention, a dense first layer, experts chosen "
                            "by biased sigmoid scores, "
                            "models/glm4_moe_lite.py), built offline from "
                            "that block's keys")
        p.add_argument("--init_from", default="",
                       help="HF GPT-2 checkpoint dir (config.json + "
                            "pytorch_model.bin) to fine-tune from; the wte is "
                            "grown for the dialog special tokens")
        p.add_argument("--model_parallel", type=int, default=1,
                       help="tensor-parallel ways for the GPT-2 path")
        p.add_argument("--attn_impl", default="dense", choices=["dense", "ring"],
                       help="ring = sequence-parallel ring attention (needs "
                            "--seq_parallel > 1; K/V blocks rotate over ICI)")
        p.add_argument("--seq_parallel", type=int, default=1,
                       help="sequence-parallel ways (mesh 'seq' axis) for "
                            "--attn_impl ring")
        p.add_argument("--mc_coef", type=float, default=0.0,
                       help="> 0 enables the next-utterance-classification "
                            "head: joint loss lm + mc_coef * mc over "
                            "--num_candidates candidate replies "
                            "(transfer-learning-conv-ai double head)")
        p.add_argument("--num_candidates", type=int, default=2,
                       help="candidates per example (gold + distractors) "
                            "when --mc_coef > 0")
        p.add_argument("--mc_hard_negatives", action="store_true",
                       help="synthetic corpus only: draw MC distractors "
                            "from other personas' replies (same word pool) "
                            "instead of a reserved vocabulary half — "
                            "mc_acc then measures persona-reply matching, "
                            "not token identity (real-json distractors are "
                            "always hard)")
        p.add_argument("--moe_experts", type=int, default=0,
                       help="> 0 swaps every 2nd block's MLP for a "
                            "Switch-style top-1 MoE with this many experts "
                            "(shard over an 'expert' mesh axis for EP)")
        p.add_argument("--moe_aux_coef", type=float, default=0.01,
                       help="weight of the MoE load-balancing aux loss")
        p.add_argument("--eval_f1", type=int, default=0,
                       help="> 0 decodes this many validation dialogs at "
                            "every eval and logs val_f1 (ConvAI2 word-level "
                            "F1 of the generated reply vs gold)")
        p.add_argument("--decode_max_new", type=int, default=32,
                       help="max generated tokens per reply for --eval_f1")
        p.add_argument("--decode_temperature", type=float, default=0.0,
                       help="0 = greedy; > 0 samples with nucleus top-p")
        p.add_argument("--decode_top_p", type=float, default=0.9)
    return p


def resolve_defaults(args: argparse.Namespace) -> argparse.Namespace:
    """Fill mode-dependent defaults so every reference flag combo maps onto a
    ModeConfig the mode library implements (see ModeConfig validation)."""
    if args.momentum_type is None:
        if args.momentum and args.momentum > 0:
            args.momentum_type = "local" if args.mode == "local_topk" else "virtual"
        else:
            args.momentum_type = "none"
    if args.error_type is None:
        args.error_type = {
            "sketch": "virtual",
            "true_topk": "virtual",
            "local_topk": "local",
        }.get(args.mode, "none")
    if args.mode in ("fedavg", "localSGD") and args.num_local_iters < 1:
        args.num_local_iters = 1
    if getattr(args, "split_compile", False):
        raise SystemExit(
            "--split_compile was removed in PR 29: the fused round compiles "
            "and runs on the chip; drop the flag")
    if getattr(args, "share_ps_gpu", False) or getattr(args, "port", 0):
        print("note: --share_ps_gpu/--port are reference-CLI compatibility "
              "no-ops (the TPU engine has no worker processes)", flush=True)
    if getattr(args, "watchdog_abort", False) and not getattr(args, "checkpoint_dir", None):
        # silently dropping the flag would leave a wedged run hanging for
        # hours — the exact outcome the operator opted out of
        raise SystemExit(
            "--watchdog_abort needs --checkpoint_dir: aborting without an "
            "emergency checkpoint would lose the run instead of resuming it"
        )
    if getattr(args, "robust_residual", "off") == "on":
        # the residual is the robust merge's error-feedback repair; with
        # no effective robust policy there is nothing to repair and the
        # flag would be a silent no-op discovered at the postmortem
        if (args.merge_policy == "sum"
                or (args.merge_policy == "trimmed"
                    and args.merge_trim == 0)):
            raise SystemExit(
                "--robust_residual on names the robust merge's error-"
                "feedback residual; with --merge_policy sum (or trimmed@0, "
                "which IS the sum program) there is no robust merge — arm "
                "--merge_policy trimmed (trim > 0) or median")
    if getattr(args, "serve_async", False):
        # the async fold is a compiled merge variant over wire tables —
        # both prerequisites must fail AT LAUNCH, not as an attribute
        # error rounds in
        if getattr(args, "serve", "off") == "off":
            raise SystemExit(
                "--serve_async is a serving mode; arm --serve inproc|socket")
        if getattr(args, "serve_payload", "announce") != "sketch":
            raise SystemExit(
                "--serve_async merges client tables as they arrive; the "
                "announce path has none — arm --serve_payload sketch")
    elif getattr(args, "serve_buffer", 0):
        raise SystemExit(
            "--serve_buffer is the --serve_async trigger size; without "
            "--serve_async the close discipline is --serve_quorum")
    if (getattr(args, "serve_pipeline", False)
            and getattr(args, "serve", "off") == "off"):
        raise SystemExit(
            "--serve_pipeline pipelines the serving rounds; arm --serve "
            "inproc|socket")
    # (the eventloop default means an unpinned non-socket run carries
    # serve_transport="eventloop" harmlessly — only a PINNED threaded
    # engine off-socket is detectably pointless now)
    if (getattr(args, "serve_transport", "eventloop") == "threaded"
            and getattr(args, "serve", "off") not in ("off", "socket")):
        raise SystemExit(
            "--serve_transport picks the SOCKET connection engine; arm "
            "--serve socket (inproc has no connections to multiplex)")
    if getattr(args, "serve_shards", 0):
        if getattr(args, "serve_shards", 0) < 2:
            raise SystemExit(
                f"--serve_shards must be >= 2 (or 0 = one listener), got "
                f"{args.serve_shards}")
        if getattr(args, "serve", "off") != "socket":
            raise SystemExit(
                "--serve_shards shards the socket ingest; arm --serve "
                "socket")
        if getattr(args, "serve_transport", "eventloop") != "eventloop":
            raise SystemExit(
                "--serve_shards runs N event-loop reactors; arm "
                "--serve_transport eventloop (thread-per-connection has "
                "no reactor to shard)")
    elif getattr(args, "serve_shard_mode", "thread") == "process":
        raise SystemExit(
            "--serve_shard_mode process needs --serve_shards >= 2 (one "
            "shard IS the plain event-loop transport)")
    if getattr(args, "serve_shard_mode", "thread") == "process":
        if (getattr(args, "serve_pipeline", False)
                or getattr(args, "serve_async", False)
                or getattr(args, "serve_edges", 0) >= 2):
            raise SystemExit(
                "--serve_shard_mode process does not compose with "
                "--serve_pipeline/--serve_async/--serve_edges yet "
                "(admission state lives in the worker processes; the "
                "cross-process band/boundary/edge disciplines are named "
                "follow-ups) — drop one of the flags")
    if getattr(args, "serve_max_conns", 0) < 0:
        raise SystemExit(
            f"--serve_max_conns must be >= 0 (0 = engine default), got "
            f"{args.serve_max_conns}")
    if getattr(args, "serve_edges", 0):
        if getattr(args, "serve_edges", 0) < 2:
            raise SystemExit(
                f"--serve_edges must be >= 2 (or 0 = flat merge), got "
                f"{args.serve_edges} (one edge IS the flat merge)")
        if getattr(args, "serve", "off") == "off":
            raise SystemExit(
                "--serve_edges is a serving topology; arm --serve "
                "inproc|socket")
        if getattr(args, "serve_payload", "announce") != "sketch":
            raise SystemExit(
                "--serve_edges aggregates client TABLES at the edge tier; "
                "the announce path has none — arm --serve_payload sketch")
        if (getattr(args, "serve_async", False)
                or getattr(args, "serve_pipeline", False)):
            raise SystemExit(
                "--serve_edges does not compose with --serve_async/"
                "--serve_pipeline yet (stale-fold edge assignment and the "
                "pipelined worker's edge timing are open follow-ups) — "
                "drop one of the flags")
    if getattr(args, "serve_fastpath", False):
        if getattr(args, "serve", "off") == "off":
            raise SystemExit(
                "--serve_fastpath is a serving-path optimization; arm "
                "--serve inproc|socket")
        if getattr(args, "serve_payload", "announce") != "sketch":
            raise SystemExit(
                "--serve_fastpath pins client TABLES into a host ring; "
                "the announce path has none — arm --serve_payload sketch")
        if getattr(args, "serve_edges", 0) >= 2:
            raise SystemExit(
                "--serve_fastpath does not compose with --serve_edges yet "
                "(the edge tier consumes the host table stack the ring "
                "replaces) — drop one of the flags")
    if getattr(args, "serve_gauntlet_workers", 2) < 1:
        raise SystemExit(
            f"--serve_gauntlet_workers must be >= 1, got "
            f"{args.serve_gauntlet_workers}")
    if getattr(args, "health_every", 0):
        if args.health_every < 0:
            raise SystemExit(
                f"--health_every must be >= 0, got {args.health_every}")
        if args.mode != "sketch":
            raise SystemExit(
                "--health_every computes SKETCH-wire quality estimators; "
                f"--mode {args.mode} has no table to estimate from")
    if getattr(args, "slo_rules", "") and getattr(args, "slo", "off") == "off":
        raise SystemExit(
            "--slo_rules names rules for the SLO engine; arm it with "
            "--slo warn|halt")
    if getattr(args, "slo", "off") != "off":
        # validate the rule grammar at launch — a typo'd rule must not be
        # a silently-absent guard discovered at the postmortem
        from ..obs.slo import parse_rules

        try:
            parse_rules(getattr(args, "slo_rules", ""))
        except ValueError as e:
            raise SystemExit(str(e)) from None
    if getattr(args, "profile_rounds", ""):
        # validate the window at launch: a typo'd spec (or a missing
        # output dir) must not surface hours later as a silently-absent
        # capture
        from ..obs.profiler import parse_rounds_spec

        try:
            parse_rounds_spec(args.profile_rounds)
        except ValueError as e:
            raise SystemExit(str(e)) from None
        if not getattr(args, "profile_dir", ""):
            raise SystemExit(
                "--profile_rounds needs --profile_dir (the capture has to "
                "be written somewhere)"
            )
    return args


def mode_config_from_args(args: argparse.Namespace, d: int) -> ModeConfig:
    return ModeConfig(
        mode=args.mode,
        d=d,
        k=min(args.k, d) if args.k else 0,
        num_rows=args.num_rows,
        num_cols=args.num_cols,
        num_blocks=args.num_blocks,
        seed=args.seed,
        momentum=args.momentum if args.momentum_type != "none" else 0.0,
        momentum_type=args.momentum_type,
        error_type=args.error_type,
        num_local_iters=args.num_local_iters if args.mode in ("fedavg", "localSGD") else 1,
        server_lr=args.server_lr if args.mode in ("fedavg", "localSGD") else 1.0,
        num_clients=args.num_clients,
        hash_family=args.hash_family,
        agg_op=args.agg_op,
        topk_impl=args.topk_impl,
        topk_recall=args.topk_recall,
        server_state=args.server_state,
    )
