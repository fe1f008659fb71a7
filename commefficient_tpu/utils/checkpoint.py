"""Checkpoint / resume (SURVEY.md §5: the reference has only a minimal model
save; the rebuild checkpoints the full server state — params, net_state,
Vvelocity/Verror, per-client state, round counter, host RNG — via orbax, so a
run can resume mid-schedule at the exact round).

Hardened for long paper-scale runs (resilience/):

- **Atomic commit**: everything (orbax tree, host RNG, meta, manifest) is
  written into a `.tmp_round_*` staging dir, then `os.rename`d to its final
  `round_*` name. A crash mid-write leaves only a staging dir, which
  `latest()`/`restore_latest()` never consider and the next save sweeps.
- **Integrity manifest**: `manifest.json` records a sha256 per file, written
  last. `verify()` checks it; `restore_latest()` walks newest-to-oldest and
  falls back LOUDLY past any checkpoint that fails verification or restore,
  so a corrupted/truncated latest checkpoint costs one checkpoint interval,
  not the run. `save()` additionally READS BACK the committed files against
  the manifest (silent-bitrot-on-write media fails the save, counted in
  `save_verify_failures()`, retried by the wrapper below).
- **Retries + fault injection**: the write path runs under
  `resilience.retry` (site "ckpt_save"), and a `FaultPlan` can inject
  transient write failures or post-commit corruption to prove the above.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import shutil
import sys
from typing import Any

import numpy as np

import jax
import orbax.checkpoint as ocp

from ..resilience import retry as rtry

MANIFEST = "manifest.json"
_TMP_PREFIX = ".tmp_round_"
# restore_latest renames a checkpoint that failed verification/restore aside
# to <name>.damaged: it stops being a restore candidate (no re-verifying a
# known-bad tree on every resume), stops counting toward save()'s keep-N
# pruning (damaged trees must not crowd out good ones), and is kept for
# post-mortem — bounded by _gc_damaged (newest KEEP_DAMAGED survive).
_DAMAGED_SUFFIX = ".damaged"
KEEP_DAMAGED = 2


def _round_dirs(ckpt_dir: str) -> list[str]:
    """Restorable-candidate names, sorted: round_* (including .displaced
    rename-aside copies — same round, same state) minus damaged ones."""
    return sorted(
        d for d in os.listdir(ckpt_dir)
        if d.startswith("round_") and not d.endswith(_DAMAGED_SUFFIX)
    )

# process-wide count of committed checkpoints that FAILED the post-commit
# read-back (save-time manifest verification): silent-bitrot-on-write media
# caught in the act. Each failure also raises inside the retry wrapper, so a
# transient flake gets re-written; `save_verify_failures()` is the count.
_VERIFY_FAILURES = 0


def save_verify_failures() -> int:
    return _VERIFY_FAILURES


class CheckpointVerifyError(RuntimeError):
    """A just-committed checkpoint failed its read-back against the sha256
    manifest — the write path (or the media under it) silently corrupted
    data. Raised from inside the retry wrapper so bounded retries re-write;
    exhaustion propagates it to the caller LOUDLY."""


def _unpadded_client_state(client_state: Any, num_clients: int) -> Any:
    """Host copy of per-client state with mesh-padding rows stripped, so a
    checkpoint is portable between sharded and unsharded sessions (the mesh
    session pads [num_clients, d] to a multiple of the client-axis size)."""
    return jax.tree.map(lambda a: np.asarray(a)[:num_clients],
                        jax.device_get(client_state))


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _write_manifest(path: str) -> None:
    sums = {}
    for root, _, files in os.walk(path):
        for f in sorted(files):
            if f == MANIFEST:
                continue
            full = os.path.join(root, f)
            sums[os.path.relpath(full, path)] = _sha256(full)
    with open(os.path.join(path, MANIFEST), "w") as f:
        json.dump({"files": sums}, f)


def verify(path: str) -> bool | None:
    """True: manifest present and every file matches. False: mismatch,
    missing file, or unreadable manifest. None: no manifest (pre-hardening
    checkpoint — can't verify; restore_latest still tries it)."""
    mf = os.path.join(path, MANIFEST)
    if not os.path.exists(mf):
        return None
    try:
        with open(mf) as f:
            sums = json.load(f)["files"]
    except Exception:
        return False
    for rel, digest in sums.items():
        full = os.path.join(path, rel)
        if not os.path.exists(full) or _sha256(full) != digest:
            return False
    return True


def save(ckpt_dir: str, session, keep: int = 3, fault_plan=None,
         retry_policy: rtry.RetryPolicy | None = None,
         verify_on_save: bool = True):
    # capture every session field under the session's mutation lock (when it
    # has one): an emergency save on the watchdog's timer thread must never
    # mix round N's params with round N-1's counter/RNG because the stalled
    # round un-stuck mid-save. jax arrays are immutable, so holding
    # references is a consistent frozen view — the expensive device_get
    # happens after the lock is released. (The references stay READABLE
    # mid-round only because sessions that arm emergency saves disable
    # state donation — FederatedSession donate_state=False; a donated
    # state would be deleted buffers for the whole in-flight round.)
    lock = getattr(session, "mutate_lock", None) or contextlib.nullcontext()
    with lock:
        rnd = session.round
        state_ref = session.state
        client_state_ref = session.client_state
        # RNG as of the last COMPLETED round (FederatedSession.rng_snapshot),
        # not the live streams: mid-round the live streams are already
        # advanced for the in-flight round, and a resumed run would train a
        # different cohort. The device key covers participation masks / DP
        # noise — without it a resumed run replays the client sequence but
        # draws FRESH dropout masks.
        rng_state, device_key = getattr(session, "rng_snapshot", None) or (
            session.rng.get_state(), session._rng_key
        )
        comm_mb_total = float(session.comm_mb_total)
        num_workers = session.num_workers
        # committed re-queue of dropped clients (cohort fault tolerance):
        # like the RNG, the COMMITTED snapshot, not the live queue a
        # prefetcher may already have served for uncommitted rounds. The
        # rounds-waiting ages ride along so a restored --requeue_policy aged
        # queue resumes each entry's REAL age instead of restarting at 1.
        requeued = [int(i) for i in
                    getattr(session, "_requeue_committed", ())]
        requeue_ages = [[int(c), int(r)] for c, r in
                        getattr(session, "_requeue_ages_committed", ())]
        # serving-layer state (serve/): the service registers a callable
        # returning a JSON-safe dict snapshotted at the committed round
        # boundary — the pending arrival queue and, in buffered-async mode,
        # the FULL stale band (parked late tables base64-exact, retained
        # screen state, straggler stash, in-flight stale-poison tables), so
        # an async preempt -> resume replays its stale folds bit-identically
        # (meta.json "serve"); None when the session is driven by the batch
        # simulator
        serve_provider = getattr(session, "serve_meta", None)
        serve_meta = serve_provider() if callable(serve_provider) else None
    final = os.path.abspath(os.path.join(ckpt_dir, f"round_{rnd:08d}"))
    staging = os.path.abspath(os.path.join(ckpt_dir, f"{_TMP_PREFIX}{rnd:08d}"))

    # snapshot the full payload ONCE, outside the retry closure: the state is
    # identical across attempts, and re-pulling hundreds of MB from the
    # device on every filesystem flake would make retries expensive exactly
    # when the run is already struggling
    payload = {
        "state": jax.device_get(state_ref),
        "round": rnd,
    }
    if client_state_ref is not None:
        payload["client_state"] = _unpadded_client_state(
            client_state_ref, session.train_set.num_clients
        )
    device_key = np.asarray(jax.device_get(device_key))

    def attempt():
        if fault_plan is not None:
            fault_plan.fire_transient("ckpt_fail", rnd)
        if os.path.isdir(staging):
            shutil.rmtree(staging)
        ocp.PyTreeCheckpointer().save(staging, payload, force=True)
        # host-side sampling RNG, so resumed runs replay the same client
        # sequence
        np.save(os.path.join(staging, "host_rng.npy"),
                np.array([rng_state[0], rng_state[1].tolist(), rng_state[2],
                          rng_state[3], rng_state[4]], dtype=object),
                allow_pickle=True)
        np.save(os.path.join(staging, "device_rng.npy"), device_key)
        # measured cumulative communication: per-round figures vary with
        # dropout survivors and local_topk's measured down-link, so
        # round * static-estimate would overstate resumed runs. num_workers
        # makes a cohort-size change across the checkpoint boundary loud at
        # restore (it breaks exact replay).
        with open(os.path.join(staging, "meta.json"), "w") as f:
            json.dump({"comm_mb_total": comm_mb_total,
                       "num_workers": num_workers,
                       "requeued": requeued,
                       "requeue_ages": requeue_ages,
                       **({"serve": serve_meta}
                          if serve_meta is not None else {})}, f)
        _write_manifest(staging)
        # overwrite (emergency save of a round already checkpointed): rename
        # the committed copy ASIDE first — a delete-then-rename would leave a
        # window (the whole rmtree) where round_N's only copy is gone, and
        # the watchdog's abort stage is designed to fire during this save.
        # The displaced name still starts with "round_", so if the process
        # dies between the two renames, restore_latest() finds the displaced
        # copy (same round, same state — both saves capture the same
        # round-boundary snapshot) instead of silently losing the round.
        old = None
        if os.path.isdir(final):
            old = final + ".displaced"
            if os.path.isdir(old):
                shutil.rmtree(old)
            os.rename(final, old)
        os.rename(staging, final)  # the atomic commit point
        if verify_on_save and verify(final) is not True:
            # read-back of the COMMITTED files against the manifest: media
            # that acknowledges writes and returns different bytes (silent
            # bitrot-on-write) must fail the SAVE loudly, not the restore
            # hours later when this checkpoint is the only copy. Counted,
            # then raised inside the retry wrapper so the write is retried.
            # Runs BEFORE the displaced copy is deleted: a corrupt re-save
            # of an already-checkpointed round must never destroy the
            # verified-good copy it displaced — put it back instead.
            global _VERIFY_FAILURES
            _VERIFY_FAILURES += 1
            if old is not None:
                shutil.rmtree(final, ignore_errors=True)
                os.rename(old, final)
            raise CheckpointVerifyError(
                f"checkpoint {final} failed post-commit read-back "
                "verification (write-path corruption); "
                f"save-verify failures this process: {_VERIFY_FAILURES}"
            )
        if old is not None:
            shutil.rmtree(old, ignore_errors=True)
        return final

    path = rtry.with_retries(
        attempt, site="ckpt_save", policy=retry_policy, seed=rnd
    )
    if fault_plan is not None:
        # post-commit damage (ckpt_corrupt/ckpt_partial) — lands AFTER the
        # manifest so verification, not luck, has to catch it
        fault_plan.corrupt_checkpoint(rnd, path)
    _prune(ckpt_dir, keep)
    return path


def latest(ckpt_dir: str) -> str | None:
    # absolute: orbax's tensorstore kvstore REJECTS relative paths at
    # restore time (save() already abspaths), so a relative --checkpoint_dir
    # would save fine and then crash every --resume
    if not os.path.isdir(ckpt_dir):
        return None
    rounds = _round_dirs(ckpt_dir)
    return os.path.abspath(os.path.join(ckpt_dir, rounds[-1])) if rounds else None


def restore(path: str, session) -> None:
    ckpt = ocp.PyTreeCheckpointer()
    template: dict[str, Any] = {
        "state": jax.device_get(session.state),
        "round": 0,
    }
    if session.client_state is not None:
        template["client_state"] = _unpadded_client_state(
            session.client_state, session.train_set.num_clients
        )
    payload = ckpt.restore(path, item=template)

    def _place(a, like):
        # Mesh-sharded leaves (TP params, client-sharded local state) keep
        # their NamedSharding; everything else stays an UNCOMMITTED plain
        # array — committing to one device would conflict with sharded
        # batches at the next jit call.
        if isinstance(like.sharding, jax.sharding.NamedSharding):
            return jax.device_put(a, like.sharding)
        return jax.numpy.asarray(a)

    session.state = jax.tree.map(_place, payload["state"], session.state)
    session.round = int(payload["round"])
    if session.client_state is not None:

        def _fit(a, like):
            a = np.asarray(a)
            pad = like.shape[0] - a.shape[0]  # re-pad for the mesh, if any
            if pad:
                a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
            return _place(a, like)

        session.client_state = jax.tree.map(_fit, payload["client_state"], session.client_state)
    rng_file = os.path.join(path, "host_rng.npy")
    if os.path.exists(rng_file):
        s = np.load(rng_file, allow_pickle=True)
        session.rng.set_state((s[0], np.asarray(s[1], dtype=np.uint32), int(s[2]),
                               int(s[3]), float(s[4])))
    key_file = os.path.join(path, "device_rng.npy")
    if os.path.exists(key_file):  # pre-hardening checkpoints lack it
        session._rng_key = jax.numpy.asarray(np.load(key_file))
    if hasattr(session, "_snapshot_rng"):
        session._snapshot_rng()  # restored streams ARE a round boundary
    meta_file = os.path.join(path, "meta.json")
    if os.path.exists(meta_file):
        with open(meta_file) as f:
            meta = json.load(f)
        session.comm_mb_total = float(meta["comm_mb_total"])
        if hasattr(session, "_requeue"):
            import collections

            requeued = [int(i) for i in meta.get("requeued", [])]
            session._requeue = collections.deque(requeued)
            session._requeue_committed = tuple(requeued)
            if hasattr(session, "_requeue_enqueued"):
                # rounds-waiting ages resume exactly (requeue_ages pairs);
                # entries a pre-age checkpoint doesn't cover restart at the
                # restored round (rounds-waiting 1 — the old behavior)
                ages = {int(c): int(r)
                        for c, r in meta.get("requeue_ages", [])}
                session._requeue_enqueued = {
                    cid: ages.get(cid, session.round) for cid in requeued}
                session._requeue_ages_committed = tuple(
                    session._requeue_enqueued.items())
        # serving-layer state for serve/ to pick up when it attaches to the
        # restored session (pending arrival queue etc.); absent = empty
        session.restored_serve_meta = meta.get("serve")
        saved_w = meta.get("num_workers")
        if saved_w is not None and saved_w != session.num_workers:
            print(
                f"warning: checkpoint {path} was written with num_workers="
                f"{saved_w} but this session runs {session.num_workers} "
                "(mesh rounding or a flag change?); the resumed run will NOT "
                "replay the uninterrupted client sequence exactly",
                flush=True,
            )
    else:
        # pre-meta checkpoint: fall back to the static per-round estimate
        # (exact when every round is uniform; overstates under dropout)
        session.comm_mb_total = session.round * session.comm_per_round["comm_total_mb"]


def _set_aside_damaged(ckpt_dir: str, name: str) -> None:
    """Rename a failed candidate to <name>.damaged: no longer a restore/
    prune candidate (see _DAMAGED_SUFFIX), kept for post-mortem until
    _gc_damaged reaps it."""
    src = os.path.join(ckpt_dir, name)
    dst = src + _DAMAGED_SUFFIX
    try:
        if os.path.isdir(dst):
            shutil.rmtree(dst, ignore_errors=True)
        os.rename(src, dst)
    except OSError as e:
        # best effort (read-only media, races): the restore fallback worked
        # either way, the rename only dedupes future verification work
        print(f"warning: could not set damaged checkpoint aside "
              f"({type(e).__name__}: {e})", file=sys.stderr, flush=True)


def _gc_damaged(ckpt_dir: str, keep: int = KEEP_DAMAGED) -> int:
    """Bound the .damaged graveyard: keep the newest `keep`, delete the
    rest, return the deletion count (loud). Without this, chaos runs with
    ckpt_corrupt plans grow one immortal damaged tree per injection."""
    names = sorted(d for d in os.listdir(ckpt_dir)
                   if d.endswith(_DAMAGED_SUFFIX))
    stale = names[:-keep] if keep > 0 else names
    for name in stale:
        shutil.rmtree(os.path.join(ckpt_dir, name), ignore_errors=True)
    if stale:
        print(
            f"checkpoint GC: deleted {len(stale)} damaged checkpoint(s) "
            f"beyond the newest {keep} ({', '.join(stale)})",
            file=sys.stderr, flush=True,
        )
    return len(stale)


def restore_latest(ckpt_dir: str, session) -> str | None:
    """Restore the newest checkpoint that verifies AND restores, falling
    back loudly past damaged ones — each failed candidate is renamed aside
    to <name>.damaged (kept for post-mortem, garbage-collected beyond the
    newest KEEP_DAMAGED) so later resumes never re-verify known-bad trees
    and save()'s keep-N pruning never counts them. Returns the restored
    path, or None when the directory holds no checkpoints (a fresh run).
    Raises when checkpoints exist(ed) but ALL are unrecoverable — silently
    restarting a long run from round 0 would be the worst outcome."""
    if not os.path.isdir(ckpt_dir):
        return None
    rounds = sorted(_round_dirs(ckpt_dir), reverse=True)
    if not rounds:
        if any(d.endswith(_DAMAGED_SUFFIX) for d in os.listdir(ckpt_dir)):
            # every checkpoint was already set aside as damaged by an
            # earlier resume: this is NOT a fresh run, refuse round 0
            raise RuntimeError(
                f"no restorable checkpoint in {ckpt_dir}: only damaged "
                "checkpoints remain (set aside by a previous restore)"
            )
        return None
    restored_path = None
    skipped = 0
    for name in rounds:
        path = os.path.abspath(os.path.join(ckpt_dir, name))
        if verify(path) is False:
            print(
                f"ERROR: checkpoint {path} FAILED integrity verification "
                "(corrupt or partial write); falling back to the previous "
                "verified-good checkpoint",
                file=sys.stderr, flush=True,
            )
            _set_aside_damaged(ckpt_dir, name)
            skipped += 1
            continue
        try:
            restore(path, session)
        except Exception as e:  # noqa: BLE001 — fall back past broken trees
            print(
                f"ERROR: checkpoint {path} failed to restore "
                f"({type(e).__name__}: {e}); falling back to the previous "
                "verified-good checkpoint",
                file=sys.stderr, flush=True,
            )
            _set_aside_damaged(ckpt_dir, name)
            skipped += 1
            continue
        restored_path = path
        break
    _gc_damaged(ckpt_dir)
    if restored_path is None:
        raise RuntimeError(
            f"no restorable checkpoint in {ckpt_dir}: all {len(rounds)} "
            "candidates failed verification or restore"
        )
    if skipped:
        print(
            f"recovered: restored {restored_path} after skipping {skipped} "
            "damaged checkpoint(s)",
            file=sys.stderr, flush=True,
        )
    return restored_path


def _prune(ckpt_dir: str, keep: int) -> None:
    names = _round_dirs(ckpt_dir)  # damaged trees never count toward keep
    stale = names[:-keep] if keep > 0 else []
    # abandoned staging dirs (crash mid-write) are dead weight: sweep them
    stale += [d for d in os.listdir(ckpt_dir) if d.startswith(_TMP_PREFIX)]
    for name in stale:
        shutil.rmtree(os.path.join(ckpt_dir, name), ignore_errors=True)
    _gc_damaged(ckpt_dir)
