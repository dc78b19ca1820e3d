"""Round-level checkpoint / resume.

The reference has no round-level checkpointing in the core FL loop (SURVEY.md
§5: only final model artifacts to S3; the LLM path leans on HF Trainer).
Here (round_idx, global variables, server state, client states, RNG key) is a
first-class checkpoint via orbax — so a 10k-round run survives preemption,
which is table stakes on TPU pods.
"""

from __future__ import annotations

import logging
import os
import shutil
import threading
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np

from ..analysis import tracesan

log = logging.getLogger("fedml_tpu.core.checkpoint")

#: orbax (0.11.32) tracks ONE process-global "current operation id" for its
#: async-save signalling (``OperationIdGenerator``).  Two managers saving
#: from two threads interleave next/get on it, and one save then waits for
#: a directory-creation signal published under the other's id until its
#: 300 s timeout — seen with two tenants journaling in one process
#: (tests/test_fleet.py stalled ~1 run in 4).  Saves are synchronous here
#: anyway, so they take turns.
_SAVE_LOCK = threading.Lock()


class RoundCheckpointer:
    def __init__(self, directory: str, keep: int = 3):
        # orbax import is deferred to first USE: the simulators import this
        # module unconditionally, but orbax is only needed when a
        # checkpoint_dir is actually configured
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self.directory = Path(directory).absolute()
        self.directory.mkdir(parents=True, exist_ok=True)
        options = ocp.CheckpointManagerOptions(max_to_keep=keep, create=True)
        self.mngr = ocp.CheckpointManager(str(self.directory), options=options)

    def save(self, round_idx: int, state: dict) -> None:
        """state: pytree dict (global_vars, server_state, client_states, key...)."""
        with tracesan.allow("checkpoint"):
            state = jax.device_get(state)
        with _SAVE_LOCK:
            try:
                self.mngr.save(round_idx, args=self._ocp.args.StandardSave(state))
            except ValueError:
                # Two managers over one directory (a lingering pre-crash
                # writer's retention GC racing the restarted server): the
                # other writer can delete a step this manager still has
                # cached, which fails save()'s old-step bookkeeping AFTER the
                # write itself was initiated.  Re-sync the cached step list
                # with the directory and retry; when the initiated write
                # already committed in the background, the step is on disk
                # and the retry is skipped.
                self.mngr.wait_until_finished()
                self.mngr.reload()
                if round_idx not in set(self.mngr.all_steps()):
                    self.mngr.save(round_idx, args=self._ocp.args.StandardSave(state))
            self.mngr.wait_until_finished()

    def _step_intact(self, step: int) -> bool:
        """Integrity probe of one step: every array/metadata file orbax
        committed must still be readable.  A crash can leave the LATEST step
        truncated (the commit marker landed but a tensor file did not flush
        fully on a hard kill) — mirroring the AOT store's corrupt-entry
        semantics, such a step is discarded rather than served.  The probe
        restores with template-less StandardRestore args: a FRESH manager
        (the recovery case) has no handler registered for a bare restore."""
        try:
            self.mngr.restore(step, args=self._ocp.args.StandardRestore())
            return True
        except Exception as e:  # orbax raises transport-specific types
            log.warning("checkpoint step %s under %s is unreadable (%s: %s) — "
                        "discarding and falling back to the previous step",
                        step, self.directory, type(e).__name__, e)
            return False

    def _discard_step(self, step: int) -> None:
        for name in (str(step), f"{step}"):
            p = self.directory / name
            if p.exists():
                shutil.rmtree(p, ignore_errors=True)
        try:
            self.mngr.reload()
        except Exception:
            pass

    def latest_round(self) -> Optional[int]:
        """Newest INTACT step (corrupt/partial steps are discarded so a
        truncated latest checkpoint falls back to the previous good one)."""
        steps = sorted(self.mngr.all_steps(), reverse=True)
        for step in steps:
            if self._step_intact(step):
                return step
            self._discard_step(step)
        return None

    def restore(self, round_idx: Optional[int] = None, template: Optional[dict] = None) -> dict:
        step = round_idx if round_idx is not None else self.latest_round()
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {self.directory}")
        if template is not None:
            template = jax.device_get(template)
            return self.mngr.restore(step, args=self._ocp.args.StandardRestore(template))
        return self.mngr.restore(step)

    def close(self) -> None:
        self.mngr.close()


class RoundCheckpointMixin:
    """Shared round-level save/resume plumbing for simulators.

    A simulator mixes this in and defines:
    - ``_ckpt_state() -> dict`` — the round-resumable state pytree (also the
      restore template), and
    - ``_apply_ckpt_state(state) -> None`` — install a restored state
      (placement/sharding concerns live here, e.g. the mesh engine re-applies
      device placement; key arrays are authoritative over config seeds).
    Requires ``self.cfg`` (checkpoint_dir/resume) and ``self.round_idx``.
    """

    def _checkpointer(self) -> "RoundCheckpointer":
        if getattr(self, "_ckpt", None) is None:
            self._ckpt = RoundCheckpointer(self.cfg.checkpoint_dir)
        return self._ckpt

    def save_checkpoint(self) -> None:
        if not self.cfg.checkpoint_dir:
            return
        self._checkpointer().save(self.round_idx, self._ckpt_state())

    def try_resume(self) -> bool:
        if not (self.cfg.checkpoint_dir and getattr(self.cfg, "resume", False)):
            return False
        if self._checkpointer().latest_round() is None:
            return False
        state = self._ckpt.restore(template=self._ckpt_state())
        self._apply_ckpt_state(state)
        return True

    def maybe_save_checkpoint(self, completed_round: int) -> None:
        """Save when the cadence says so: every ``checkpoint_every_rounds``
        completed rounds and at the final round (one cadence definition for
        every simulator)."""
        every = getattr(self.cfg, "checkpoint_every_rounds", 0)
        if every and (
            (completed_round + 1) % every == 0
            or completed_round == self.cfg.comm_round - 1
        ):
            self.save_checkpoint()
