"""Checkpoint integrity: retries with backoff, checksum manifests, walk-back.

Three failure modes of persistent storage under long runs, and their
treatment here:

* **transient errors** (flaky NFS/GCS, momentary quota): every save/restore
  attempt runs under `retry_transient` — exponential backoff on ``OSError``,
  bounded attempts, then the error propagates (it was not transient).
* **silent corruption** (bit rot, torn replication): every committed step
  gets a ``manifest_<step>.json`` sidecar of per-file sha256 digests,
  written atomically after orbax finalizes; `verify` recomputes digests
  before a restore touches the arrays.
* **partial writes** (a kill mid-save): the step exists but is not
  restorable. `restore_latest_verified` walks ``all_steps()`` newest-first,
  skipping steps that fail verification *or* whose restore raises, and
  lands on the newest verifiable checkpoint instead of killing the run.

Steps predating this manager carry no manifest; they are accepted with a
warning (the walk-back still catches them if they fail to restore) so
existing runs resume unchanged.
"""

from __future__ import annotations

import hashlib
import json
import time
import warnings
from pathlib import Path
from typing import Any, Callable

import jax
import orbax.checkpoint as ocp

from ..training.checkpoint import TrainCheckpointManager
from ..utils.misc import atomic_write_json
from ..utils.scopes import host_spanned
from . import faults

__all__ = [
    "ReliableCheckpointManager",
    "decode_resume_metadata",
    "resume_training_state",
    "retry_transient",
]


def decode_resume_metadata(meta: dict | None) -> tuple[int, int]:
    """``(resume_epoch, skip_batches)`` from a checkpoint metadata sidecar —
    the one decoding of the resume coordinates (pretrain resume, fine-tune
    resume, and divergence rollback all route through here, so they cannot
    disagree). An epoch-complete checkpoint resumes at the next epoch's
    start; a mid-epoch one re-enters its epoch past the batches already
    trained on."""
    meta = meta or {}
    if meta.get("epoch_complete", True):
        return int(meta.get("epoch", 0)) + 1, 0
    return int(meta.get("epoch", 0)), int(meta.get("step_in_epoch", 0))


@host_spanned("startup/restore", id="startup")
def resume_training_state(
    ckpt_mgr: "ReliableCheckpointManager", state: Any, place_state: Callable[[Any], Any]
) -> tuple[Any, int, int, int]:
    """The training loops' shared auto-resume: walk-back restore of the
    newest verifiable checkpoint with readable resume metadata, re-placed on
    the caller's mesh. Returns ``(state, restored_step, start_epoch,
    skip_batches)``."""
    from flax import serialization

    import jax

    template = serialization.to_state_dict(jax.device_get(state))
    restored_sd, step = ckpt_mgr.restore_latest_verified(template, require_metadata=True)
    state = place_state(serialization.from_state_dict(jax.device_get(state), restored_sd))
    start_epoch, skip = decode_resume_metadata(ckpt_mgr.metadata(step))
    print(
        f"Resumed from checkpoint at step {step} "
        f"(epoch {start_epoch}, skipping {skip} batches)"
    )
    return state, step, start_epoch, skip


def retry_transient(
    fn: Callable[[], Any],
    *,
    retries: int = 3,
    backoff_base: float = 0.5,
    backoff_max: float = 8.0,
    sleep: Callable[[float], None] = time.sleep,
    describe: str = "checkpoint I/O",
) -> Any:
    """Runs ``fn`` with exponential backoff on ``OSError``.

    ``retries`` counts *re*-attempts: the operation runs at most
    ``retries + 1`` times, sleeping ``min(backoff_base * 2**attempt,
    backoff_max)`` between attempts. Non-``OSError`` failures propagate
    immediately — only plausibly-transient filesystem errors are retried.
    """
    for attempt in range(retries + 1):
        try:
            return fn()
        except OSError as e:
            if attempt == retries:
                raise
            delay = min(backoff_base * (2.0**attempt), backoff_max)
            warnings.warn(
                f"{describe} failed (attempt {attempt + 1}/{retries + 1}): {e}; "
                f"retrying in {delay:.2f}s",
                RuntimeWarning,
                stacklevel=2,
            )
            sleep(delay)


def _file_sha256(fp: Path, chunk: int = 1 << 20) -> str:
    h = hashlib.sha256()
    with open(fp, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                break
            h.update(block)
    return h.hexdigest()


class ReliableCheckpointManager(TrainCheckpointManager):
    """`TrainCheckpointManager` hardened for pod-scale runs.

    Saves block on orbax finalization so the manifest hashes the *committed*
    files (train loops already save at a drained cadence, so the lost
    async overlap is one checkpoint interval's tail). Restores should go
    through `restore_latest_verified`; the base `restore` stays available
    for explicit-step surgery.
    """

    def __init__(
        self,
        ckpt_dir: Path | str,
        max_to_keep: int = 2,
        save_interval_steps: int = 1,
        *,
        retries: int = 3,
        backoff_base: float = 0.5,
        backoff_max: float = 8.0,
        sleep: Callable[[float], None] = time.sleep,
    ):
        super().__init__(ckpt_dir, max_to_keep=max_to_keep, save_interval_steps=save_interval_steps)
        self._retries = retries
        self._backoff_base = backoff_base
        self._backoff_max = backoff_max
        self._sleep = sleep
        self._save_calls = 0

    # ------------------------------------------------------------------ save
    def save(self, step: int, state: Any, metadata: dict | None = None) -> bool:
        save_index = self._save_calls
        self._save_calls += 1
        attempt_counter = {"n": 0}

        def attempt() -> bool:
            this_attempt = attempt_counter["n"]
            attempt_counter["n"] += 1
            faults.maybe_fail_save(save_index, this_attempt)
            saved_ = super(ReliableCheckpointManager, self).save(step, state, metadata)
            if saved_:
                # Orbax saves are async: a flaky filesystem surfaces its
                # OSError from the background array write HERE, not from the
                # enqueue above — waiting inside the attempt is what makes
                # the real transient-write failure retryable (and the
                # manifest below requires finalized files anyway).
                self.wait_until_finished()
            return saved_

        saved = retry_transient(
            attempt,
            retries=self._retries,
            backoff_base=self._backoff_base,
            backoff_max=self._backoff_max,
            sleep=self._sleep,
            describe=f"checkpoint save (step {step})",
        )
        if saved:
            # The deterministic crash window sits exactly here: arrays
            # committed on disk, manifest not yet written.
            faults.maybe_kill_during_save(self.ckpt_dir, step, save_index)
            retry_transient(
                lambda: self._write_manifest(step),
                retries=self._retries,
                backoff_base=self._backoff_base,
                backoff_max=self._backoff_max,
                sleep=self._sleep,
                describe=f"checkpoint manifest (step {step})",
            )
            faults.maybe_corrupt_after_save(self.ckpt_dir, step, save_index)
        return saved

    # -------------------------------------------------------------- manifest
    def _manifest_fp(self, step: int) -> Path:
        return self.ckpt_dir / f"manifest_{step}.json"

    def _step_dir(self, step: int) -> Path:
        return self.ckpt_dir / str(step)

    def _write_manifest(self, step: int) -> None:
        if jax.process_index() != 0:
            return  # shared-fs sidecar: one writer (see TrainCheckpointManager.save)
        step_dir = self._step_dir(step)
        if not step_dir.is_dir():
            return  # layout without per-step dirs: nothing to attest
        files = {}
        for fp in sorted(p for p in step_dir.rglob("*") if p.is_file()):
            rel = fp.relative_to(step_dir).as_posix()
            files[rel] = {"sha256": _file_sha256(fp), "bytes": fp.stat().st_size}
        atomic_write_json(
            self._manifest_fp(step), {"step": step, "algo": "sha256", "files": files}
        )

    def verify(self, step: int) -> bool:
        """Recomputes the step's digests against its manifest.

        Missing manifest → accepted with a warning (pre-manifest legacy
        steps); present-but-unreadable or mismatching → False.
        """
        return self._verify_status(step) != "failed"

    def _verify_status(self, step: int) -> str:
        """``"verified"`` (manifest matched), ``"legacy"`` (no manifest —
        accepted but unproven), or ``"failed"`` (provably corrupt). The
        distinction drives the walk-back deletion policy: only steps the
        checksums actually vouch for are kept when their restore fails."""
        fp = self._manifest_fp(step)
        if not fp.exists():
            warnings.warn(
                f"checkpoint step {step} has no integrity manifest; accepting unverified",
                RuntimeWarning,
                stacklevel=2,
            )
            return "legacy"
        try:
            with open(fp) as f:
                manifest = json.load(f)
            files = manifest["files"]
        except (OSError, json.JSONDecodeError, KeyError, UnicodeDecodeError) as e:
            warnings.warn(f"unreadable manifest for step {step}: {e}", RuntimeWarning, stacklevel=2)
            return "failed"
        step_dir = self._step_dir(step)
        for rel, meta in files.items():
            f = step_dir / rel
            if not f.is_file():
                warnings.warn(f"step {step}: missing file {rel}", RuntimeWarning, stacklevel=2)
                return "failed"
            if f.stat().st_size != meta["bytes"] or _file_sha256(f) != meta["sha256"]:
                warnings.warn(
                    f"step {step}: checksum mismatch on {rel}", RuntimeWarning, stacklevel=2
                )
                return "failed"
        return "verified"

    # --------------------------------------------------------------- restore
    def restore_latest_verified(
        self, state_template: Any, *, require_metadata: bool = False
    ) -> tuple[Any, int]:
        """Restores the newest checkpoint that passes verification.

        Walks ``all_steps()`` newest-first; a step that fails checksum
        verification, or whose restore raises (truncated/partial writes on
        legacy manifest-less steps), is skipped with a warning instead of
        killing the run. With ``require_metadata`` (the training loops'
        resume paths), a step whose metadata sidecar is missing/undecodable
        is also skipped: its resume coordinates are gone, and silently
        defaulting them would reset the epoch counter under epoch-7 weights.
        Raises ``FileNotFoundError`` when nothing restorable remains.

        Skipped-step disposal: provably-bad newer steps (checksum-failed,
        manifest-less torn writes, lost metadata) are deleted after a
        successful restore — orbax's monotonic-step contract ignores any
        ``save(step <= latest_step)``, so leaving them would silently no-op
        every re-save of the retrained window. A checksum-**verified** step
        whose restore failed is presumed transiently unreadable and kept for
        the next relaunch, at the documented cost that saves below it are
        skipped until training passes it again.
        """
        steps = sorted(self.all_steps(), reverse=True)
        if not steps:
            raise FileNotFoundError(f"No checkpoints found under {self.ckpt_dir}")
        skipped: dict[int, str] = {}  # step -> why, for the disposal policy
        for step in steps:
            status = self._verify_status(step)
            if status == "failed":
                warnings.warn(
                    f"skipping corrupt/unverifiable checkpoint step {step}; walking back",
                    RuntimeWarning,
                    stacklevel=2,
                )
                skipped[step] = "failed"
                continue
            if require_metadata and self.metadata(step) is None:
                warnings.warn(
                    f"checkpoint step {step} has no readable resume metadata; walking back",
                    RuntimeWarning,
                    stacklevel=2,
                )
                # A checksum-VERIFIED step with an unreadable sidecar is not
                # disposable: the arrays are provably good and the sidecar
                # read may have failed transiently — keep it (same policy as
                # a verified step whose restore raised). Only unproven steps
                # are tagged for deletion.
                skipped[step] = "verified" if status == "verified" else "no-metadata"
                continue
            try:
                state = retry_transient(
                    lambda: self._mgr.restore(
                        step, args=ocp.args.PyTreeRestore(state_template)
                    ),
                    # A torn write (e.g. a kill mid-save on a manifest-less
                    # step) raises OSError too, and no amount of backoff
                    # repairs it — one retry covers the genuinely transient
                    # case without stalling the walk-back on every corrupt
                    # step it passes.
                    retries=min(self._retries, 1),
                    backoff_base=self._backoff_base,
                    backoff_max=self._backoff_max,
                    sleep=self._sleep,
                    describe=f"checkpoint restore (step {step})",
                )
            except Exception as e:  # orbax surfaces corruption as various types
                warnings.warn(
                    f"restore of checkpoint step {step} failed ({type(e).__name__}: {e}); "
                    "walking back to an earlier step",
                    RuntimeWarning,
                    stacklevel=2,
                )
                skipped[step] = status  # "verified" or "legacy"
                continue
            self._dispose_skipped(skipped, restored_step=step)
            return state, step
        raise FileNotFoundError(
            f"No verifiable checkpoint could be restored under {self.ckpt_dir} "
            f"(tried steps {steps})"
        )

    def _dispose_skipped(self, skipped: dict[int, str], restored_step: int) -> None:
        """Applies the walk-back disposal policy (process 0 only — the
        checkpoint store is shared across a pod)."""
        if jax.process_index() != 0:
            return
        for newer, why in sorted(skipped.items()):
            if why == "verified":
                warnings.warn(
                    f"checkpoint step {newer} is checksum-verified but was skipped "
                    f"(restore or sidecar read failed, presumed transient); keeping "
                    f"it — NOTE: re-saves at steps <= {newer} are skipped until "
                    f"training passes it",
                    RuntimeWarning,
                    stacklevel=3,
                )
                continue
            try:
                self._mgr.delete(newer)
                warnings.warn(
                    f"deleted unrestorable checkpoint step {newer} "
                    f"(walked back to {restored_step})",
                    RuntimeWarning,
                    stacklevel=3,
                )
            except Exception as e:  # pragma: no cover - fs-dependent
                warnings.warn(
                    f"could not delete unrestorable checkpoint step {newer}: {e}",
                    RuntimeWarning,
                    stacklevel=3,
                )
        self._prune_metadata()
