"""Async checkpoint/resume for training: survive preemption bit-exactly.

TPU fleets are preemptible (Podracer, arXiv:2104.06272, makes
checkpoint-resume the load-bearing answer), so a multi-hour
``build_gpt_train`` run must be able to die at any step and continue
as if nothing happened.  Two pieces:

- :class:`TrainCheckpointer` — snapshots the **full** resume state
  (the donated :class:`~ray_tpu.models.training.TrainState` — params,
  opt state, step counter — plus caller extras like the data cursor
  and PRNG key) to host on the training thread, then hands the disk
  write to a **background thread**: the steady-state step loop only
  pays the device->host copy every ``RAY_TPU_CKPT_EVERY`` steps, never
  the filesystem.  Writes go through the existing orbax/npz path
  (``train/checkpoint.py:save_pytree``) into
  ``train/checkpoint_manager.py`` retention (keep
  ``RAY_TPU_CKPT_KEEP`` newest), so the on-disk layout is the same
  ``checkpoint_NNNNNN`` family every other trainer here writes.

- :meth:`TrainCheckpointer.restore_latest` — walks the retained
  snapshots newest-first, **validating** each restored tree against
  the live state's structure/shapes/dtypes, and falls back *loudly* to
  the previous retained snapshot on a torn or corrupt one (truncated
  orbax dir, npz/sidecar mismatch) instead of crashing or silently
  loading garbage.

Resume is bit-exact by construction: the snapshot is taken *between*
steps (after step N's state materialized, before step N+1 donates it),
and the data cursor restores the exact batch sequence — the loss
sequence after :func:`run_train_ckpt_loop` resumes is identical to an
uninterrupted run's (asserted in ``tests/test_resilience.py``).

Failure policy: a checkpoint write that raises (disk full, injected
``ckpt.write`` fault) is counted and warned, never propagated — the
checkpointer must not kill the run it exists to protect.
"""

from __future__ import annotations

import os
import queue
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ray_tpu.resilience.config import resilience_config
from ray_tpu.train.checkpoint import (Checkpoint, load_pytree,
                                      save_pytree)
from ray_tpu.train.checkpoint_manager import CheckpointManager
from ray_tpu.train.config import CheckpointConfig
from ray_tpu.util import chaos

_STATE_NAME = "train_state"


def _mesh_spec(mesh):
    """Mesh / MeshSpec / None -> MeshSpec or None (the sidecar form)."""
    if mesh is None:
        return None
    from ray_tpu.parallel.mesh import MeshSpec
    return MeshSpec.from_mesh(mesh)


def _host_tree(tree):
    """Device pytree -> host (numpy) pytree.  Blocks until the leaves'
    producing computation is done — which is exactly the between-steps
    barrier that makes the snapshot a consistent cut.  (Plain
    ``np.asarray``: ``ascontiguousarray`` would promote the 0-d step
    counter to shape ``(1,)`` and break shape validation on restore.)"""
    import jax
    return jax.tree.map(lambda x: np.asarray(x), tree)


def _validate_tree(restored, example) -> None:
    """Raise ``ValueError`` unless ``restored`` matches ``example``'s
    structure and per-leaf shape/dtype.  The npz fallback path can
    deserialize a *wrong* tree without erroring (the arrays load fine,
    they just aren't this model's), and training on garbage params is
    strictly worse than failing over to an older snapshot."""
    import jax
    rl, rt = jax.tree.flatten(restored)
    el, et = jax.tree.flatten(example)
    if rt != et:
        raise ValueError(f"checkpoint tree structure mismatch: "
                         f"{rt} != {et}")
    for i, (r, e) in enumerate(zip(rl, el)):
        r_shape, e_shape = np.shape(r), np.shape(e)
        r_dtype = np.asarray(r).dtype if not hasattr(r, "dtype") \
            else r.dtype
        e_dtype = np.asarray(e).dtype if not hasattr(e, "dtype") \
            else e.dtype
        if tuple(r_shape) != tuple(e_shape) or \
                np.dtype(r_dtype) != np.dtype(e_dtype):
            raise ValueError(
                f"checkpoint leaf {i} mismatch: restored "
                f"{r_dtype}{list(r_shape)} vs expected "
                f"{e_dtype}{list(e_shape)}")


def _truncate_dir(path: str) -> None:
    """Corrupt a just-written checkpoint (the ``ckpt.truncate`` fault
    action): delete the second half of its files, depth-first — enough
    to tear either the orbax layout or the npz+sidecar pair."""
    files: List[str] = []
    for root, _dirs, names in os.walk(path):
        files.extend(os.path.join(root, n) for n in sorted(names))
    for f in files[len(files) // 2:] or files:
        try:
            os.remove(f)
        except OSError:
            pass


class TrainCheckpointer:
    """Async snapshot writer + corrupt-tolerant restorer.

    ``maybe_save(state, step=...)`` is the hot-path call: a no-op
    unless ``step`` is a multiple of ``every``; on trigger it copies
    the state to host (the only cost the step loop sees) and enqueues
    the write.  The background thread persists through
    ``save_pytree`` and registers with a
    :class:`~ray_tpu.train.checkpoint_manager.CheckpointManager`
    (``resume=True``: a restarted process adopts the prior run's
    snapshots — that is the whole point here), which prunes to the
    ``keep`` newest.  ``flush()`` blocks until the write queue drains
    (call before measuring or exiting); ``close()`` flushes and stops
    the thread.

    The write queue is bounded at 2: if writes are slower than the
    cadence, ``save`` blocks rather than buffering an unbounded trail
    of host snapshots (each is a full model copy).
    """

    def __init__(self, directory: Optional[str] = None, *,
                 every: Optional[int] = None,
                 keep: Optional[int] = None,
                 mesh=None,
                 accum_steps: Optional[int] = None,
                 label: str = "train",
                 telemetry=None):
        rcfg = resilience_config()
        self.directory = directory or rcfg.ckpt_dir
        if self.directory is None:
            raise ValueError("TrainCheckpointer needs a directory "
                             "(argument or RAY_TPU_CKPT_DIR)")
        self.every = rcfg.ckpt_every if every is None else int(every)
        if self.every < 0:
            raise ValueError(f"every must be >= 0, got {self.every} "
                             "(check RAY_TPU_CKPT_EVERY)")
        keep = rcfg.ckpt_keep if keep is None else int(keep)
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep} "
                             "(check RAY_TPU_CKPT_KEEP)")
        os.makedirs(self.directory, exist_ok=True)
        self.manager = CheckpointManager(
            self.directory, CheckpointConfig(num_to_keep=keep),
            resume=True)
        from ray_tpu.telemetry.ckpt import CkptTelemetry
        from ray_tpu.telemetry.config import TelemetryConfig
        config = (TelemetryConfig(enabled=bool(telemetry))
                  if isinstance(telemetry, bool) else None)
        self.telemetry = CkptTelemetry(label=label, config=config)
        # default elastic sidecar (per-save mesh=/accum_steps= override
        # it — the elastic loop's topology changes mid-run)
        self.mesh_spec = _mesh_spec(mesh)
        self.accum_steps = (None if accum_steps is None
                            else int(accum_steps))
        self.write_errors: List[str] = []
        self._q: "queue.Queue" = queue.Queue(maxsize=2)
        self._lock = threading.Lock()   # manager index/registration
        self._thread = threading.Thread(target=self._writer,
                                        daemon=True,
                                        name="ckpt-writer")
        self._thread.start()

    # -------------------------------------------------------- hot path
    def maybe_save(self, state, *, step: int,
                   extras: Optional[Dict[str, Any]] = None,
                   mesh=None,
                   accum_steps: Optional[int] = None) -> bool:
        """Checkpoint iff ``every`` is on and ``step % every == 0``.
        Returns True when a snapshot was taken (write still async)."""
        if not self.every or step % self.every:
            return False
        self.save(state, step=step, extras=extras, mesh=mesh,
                  accum_steps=accum_steps)
        return True

    def save(self, state, *, step: int,
             extras: Optional[Dict[str, Any]] = None,
             mesh=None,
             accum_steps: Optional[int] = None) -> None:
        """Snapshot now: host copy on this thread, write in background.

        ``mesh``/``accum_steps`` override the constructor defaults for
        this snapshot's elastic sidecar — the writing topology and
        accumulation factor ride the checkpoint metadata so a restore
        onto a *different* mesh is a decision
        (:meth:`restore_latest` ``reshard=True``), never an accident."""
        payload = {
            "state": _host_tree(state),
            "extras": {k: np.asarray(v)
                       for k, v in (extras or {}).items()},
        }
        spec = _mesh_spec(mesh) if mesh is not None else self.mesh_spec
        accum = self.accum_steps if accum_steps is None \
            else int(accum_steps)
        sidecar: Dict[str, Any] = {}
        if spec is not None:
            sidecar["mesh"] = spec.to_dict()
        if accum is not None:
            sidecar["accum_steps"] = accum
        self._q.put((payload, int(step), sidecar))

    # ------------------------------------------------------- background
    def _writer(self) -> None:
        while True:
            job = self._q.get()
            if job is None:
                self._q.task_done()
                return
            payload, step, sidecar = job
            try:
                t0 = time.monotonic()
                chaos.maybe_fail("ckpt.write")
                with self._lock:
                    idx = self.manager._index
                    dest = os.path.join(self.directory,
                                        f"checkpoint_{idx:06d}")
                    save_pytree(payload, dest, name=_STATE_NAME)
                    ckpt_obj = Checkpoint(dest)
                    if sidecar:
                        # the elastic block rides the checkpoint's own
                        # .metadata.json (one JSON for both the orbax
                        # and npz state formats)
                        ckpt_obj.set_metadata({"elastic": sidecar})
                    if chaos.should_fire("ckpt.truncate"):
                        _truncate_dir(dest)
                    self.manager.register(ckpt_obj,
                                          metrics={"step": step})
                self.telemetry.record_write(time.monotonic() - t0,
                                            step=step)
            except Exception as e:  # noqa: BLE001 — never kill the run
                self.telemetry.record_failure()
                self.write_errors.append(f"step {step}: {e!r}")
                print(f"checkpoint write for step {step} failed "
                      f"({e!r}); training continues on the previous "
                      "retained snapshot", file=sys.stderr)
            finally:
                self._q.task_done()

    def flush(self) -> None:
        """Block until every enqueued write has been attempted."""
        self._q.join()

    def close(self) -> None:
        self.flush()
        self._q.put(None)
        self._thread.join(timeout=30)

    def __enter__(self) -> "TrainCheckpointer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ---------------------------------------------------------- restore
    def restore_latest(self, example=None, *, mesh=None,
                       reshard: bool = False
                       ) -> Optional[Dict[str, Any]]:
        """Newest restorable snapshot, or None when the directory holds
        nothing usable.

        Walks retained checkpoints newest-first; each candidate is
        loaded and (when ``example`` — a live payload-shaped pytree —
        is given) validated leaf-by-leaf.  A candidate that fails to
        load **or** validate is skipped with a loud stderr warning and
        the walk falls back to the previous retained snapshot: a
        truncated orbax dir or an npz whose sidecar disagrees with the
        model must cost one checkpoint interval of progress, not the
        run (and must never train on silently-wrong arrays).

        ``mesh``: the topology the caller intends to restore onto.
        When the snapshot's elastic sidecar records a *different*
        writing mesh, restore raises a typed
        :class:`~ray_tpu.resilience.elastic.MeshMismatchError` unless
        ``reshard=True`` — the state's host arrays place onto any
        dividing mesh (``resilience.elastic.reshard_state``), but that
        must be a decision, not a drive-by.  Snapshots written before
        the sidecar existed (no ``elastic`` block) restore as before
        — back-compat over strictness for data that cannot know.

        Returns ``{"state", "extras", "step", "path", "mesh",
        "accum_steps"}`` (``mesh``: the recorded
        :class:`~ray_tpu.parallel.mesh.MeshSpec` or None;
        ``accum_steps``: the recorded factor or None).
        """
        self.flush()
        with self._lock:
            candidates = list(self.manager.best_checkpoints())
        for ckpt, metrics in candidates:     # newest first (recency)
            # the sidecar is one small JSON — check the topology
            # BEFORE deserializing a potentially multi-GB state that
            # a mismatch would only throw away
            sidecar = ckpt.get_metadata().get("elastic", {})
            recorded = sidecar.get("mesh")
            if recorded is not None:
                from ray_tpu.parallel.mesh import MeshSpec
                recorded = MeshSpec.from_dict(recorded)
                if mesh is not None and not reshard:
                    current = _mesh_spec(mesh)
                    if recorded != current:
                        # NOT a fall-back case: every retained
                        # snapshot of this run was written on the same
                        # mesh — walking older ones would just repeat
                        # the mismatch against staler state
                        from ray_tpu.resilience.elastic import \
                            MeshMismatchError
                        raise MeshMismatchError(recorded, current)
            try:
                payload = load_pytree(ckpt.path, name=_STATE_NAME,
                                      target=example)
                if example is not None:
                    _validate_tree(payload, example)
            except Exception as e:  # noqa: BLE001 — fall back, loudly
                print(f"checkpoint restore from {ckpt.path} failed "
                      f"({e!r}); falling back to the previous "
                      "retained snapshot", file=sys.stderr)
                continue
            return {"state": payload["state"],
                    "extras": payload.get("extras", {}),
                    "step": int(metrics.get("step", -1)),
                    "path": ckpt.path,
                    "mesh": recorded,
                    "accum_steps": sidecar.get("accum_steps")}
        return None


def run_train_ckpt_loop(cfg, mesh=None, *, steps: int,
                        batch_size: int = 4, seq_len: int = 32,
                        seed: int = 0,
                        ckpt: Optional[TrainCheckpointer] = None,
                        resume: bool = False,
                        fns: Optional[Dict[str, Callable]] = None,
                        on_step: Optional[Callable[[int], None]] = None
                        ) -> Dict[str, Any]:
    """A checkpointed synthetic-LM training loop — the resume-proof
    driver for tests and preempted-run recovery.

    Every batch is a pure function of ``(seed, cursor)`` —
    ``synthetic_lm_batch(fold_in(data_key, cursor))`` — so the data
    cursor in the checkpoint extras pins the exact batch sequence: a
    resumed run replays from the snapshot's cursor and its loss
    sequence is **bit-exact** against the uninterrupted run (same
    jitted step, same state bits, same batches).

    ``resume=True`` restores the newest valid snapshot from ``ckpt``
    (corrupt ones fall back, see
    :meth:`TrainCheckpointer.restore_latest`) and continues from its
    cursor; with nothing restorable it starts from scratch.
    ``on_step(cursor)`` is a post-step test hook (kill points).
    """
    import jax

    from ray_tpu.models import training

    if mesh is None:
        from ray_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    fns = fns or training.build_gpt_train(cfg, mesh, telemetry=False)
    state = fns["init_fn"](jax.random.PRNGKey(seed))
    data_key = jax.random.PRNGKey(seed + 1)
    cursor = 0
    restored_from = None
    if resume:
        if ckpt is None:
            raise ValueError("resume=True needs a TrainCheckpointer")
        example = {"state": state,
                   "extras": {"data_cursor": np.asarray(0)}}
        restored = ckpt.restore_latest(example=example, mesh=mesh)
        if restored is not None:
            state = jax.device_put(restored["state"],
                                   fns["state_shardings"])
            cursor = int(restored["extras"]["data_cursor"])
            restored_from = restored["path"]
    start = cursor
    losses: List[float] = []
    step_fn = fns["raw_step_fn"] if "raw_step_fn" in fns \
        else fns["step_fn"]
    while cursor < steps:
        batch = training.synthetic_lm_batch(
            jax.random.fold_in(data_key, cursor), batch_size, seq_len,
            cfg.vocab_size)
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        cursor += 1
        if ckpt is not None:
            ckpt.maybe_save(state, step=cursor,
                            extras={"data_cursor": cursor},
                            mesh=mesh,
                            accum_steps=fns.get("accum_steps"))
        if on_step is not None:
            on_step(cursor)
    if ckpt is not None:
        ckpt.flush()
    return {
        "losses": losses,
        "start_step": start,
        "steps_run": cursor - start,
        "restored_from": restored_from,
        "final_step": int(np.asarray(state.step)),
        "checkpoint": (ckpt.telemetry.summary() if ckpt is not None
                       else {"enabled": False}),
    }


def run_train_stream_loop(cfg, mesh=None, *, steps: int,
                          batch_size: int = 4, seq_len: int = 32,
                          seed: int = 0,
                          source=None,
                          ckpt: Optional[TrainCheckpointer] = None,
                          resume: bool = False,
                          fns: Optional[Dict[str, Callable]] = None,
                          on_step: Optional[Callable[[int], None]] = None,
                          loader_kwargs: Optional[Dict[str, Any]] = None
                          ) -> Dict[str, Any]:
    """The r17 acceptance driver: :func:`run_train_ckpt_loop` with a
    **streaming** source instead of the trivial fold-in cursor.

    Batches come from :class:`ray_tpu.data.StreamingLoader` — shard
    readers, sample packing (segment-masked ``[B, S]``), the bounded
    prefetch queue — and every delivered batch carries the
    :class:`~ray_tpu.data.StreamCursor` that regenerates its
    successors.  That cursor (fixed-capacity uint8 image: per-shard
    offsets + packer residue; in-flight prefetched batches replay by
    construction) rides the checkpoint ``extras``, so a run killed at
    any step — including via SIGKILL with reads in flight — resumes
    with a loss sequence float-equal to the uninterrupted run's.

    ``source`` defaults to a :class:`~ray_tpu.data.SyntheticDocs`
    corpus derived from ``seed``; pass any
    :class:`~ray_tpu.data.DocumentSource` for real shards.
    ``loader_kwargs`` forwards to the loader (``readers=``, ``pack=``,
    ``prefetch=``, ``retries=`` ...).
    """
    import jax

    from ray_tpu.data.source import SyntheticDocs
    from ray_tpu.data.stream import StreamCursor, StreamingLoader
    from ray_tpu.models import training

    if mesh is None:
        from ray_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    fns = fns or training.build_gpt_train(cfg, mesh, telemetry=False)
    state = fns["init_fn"](jax.random.PRNGKey(seed))
    if source is None:
        source = SyntheticDocs(seed + 1, num_shards=4,
                               docs_per_shard=256,
                               vocab=cfg.vocab_size,
                               min_len=max(2, seq_len // 8),
                               max_len=max(3, (3 * seq_len) // 4))
    lkw = dict(loader_kwargs or {})
    capacity = lkw.pop("cursor_capacity", None)
    if capacity is None:
        from ray_tpu.data.stream import CURSOR_CAPACITY
        capacity = CURSOR_CAPACITY
    cursor = None
    restored_from = None
    if resume:
        if ckpt is None:
            raise ValueError("resume=True needs a TrainCheckpointer")
        example = {"state": state,
                   "extras": {"data_cursor":
                              np.zeros(capacity, np.uint8)}}
        restored = ckpt.restore_latest(example=example, mesh=mesh)
        if restored is not None:
            state = jax.device_put(restored["state"],
                                   fns["state_shardings"])
            cursor = StreamCursor.from_array(
                restored["extras"]["data_cursor"])
            restored_from = restored["path"]
    start = cursor.batches if cursor is not None else 0
    losses: List[float] = []
    step_fn = fns["raw_step_fn"] if "raw_step_fn" in fns \
        else fns["step_fn"]
    with StreamingLoader(source, batch_size=batch_size,
                         seq_len=seq_len, seed=seed, cursor=cursor,
                         cursor_capacity=capacity, **lkw) as loader:
        step = start
        while step < steps:
            try:
                sb = loader.next()
            except StopIteration:
                # a finite stream (loader_kwargs epochs=) drained
                # early: surface it typed, never as a bare
                # StopIteration (PEP 479 would mangle it inside
                # generators)
                from ray_tpu.data.stream import DataPlaneError
                raise DataPlaneError(
                    f"streaming source drained at batch {step} "
                    f"before the requested {steps} steps")
            state, metrics = step_fn(state, sb.batch)
            losses.append(float(metrics["loss"]))
            step = sb.cursor.batches
            if ckpt is not None:
                ckpt.maybe_save(state, step=step,
                                extras={"data_cursor": sb.cursor_array},
                                mesh=mesh,
                                accum_steps=fns.get("accum_steps"))
            if on_step is not None:
                on_step(step)
        data_summary = loader.telemetry.summary()
    if ckpt is not None:
        ckpt.flush()
    return {
        "losses": losses,
        "start_step": start,
        "steps_run": step - start,
        "restored_from": restored_from,
        "final_step": int(np.asarray(state.step)),
        "data": data_summary,
        "checkpoint": (ckpt.telemetry.summary() if ckpt is not None
                       else {"enabled": False}),
    }
