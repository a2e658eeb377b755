"""Orbax/tensorstore checkpoint engine.

The TPU-native ``TorchCheckpointEngine`` equivalent: sharded arrays are
written by every host in parallel to a tensorstore layout (each host writes
its addressable shards — the same property the reference gets from per-rank
``bf16_zero_pp_rank_X...`` files, ``engine.py:3471``), and restored with
arbitrary resharding — which also subsumes the reference's universal
checkpoint reshape tooling (``deepspeed/checkpoint/ds_to_universal.py``) for
mesh-shape changes.
"""

import os
import pickle

import jax
import numpy as np

from .checkpoint_engine import CheckpointEngine
from ..resilience.errors import CheckpointCorruptError
from ...utils.logging import logger


class OrbaxCheckpointEngine(CheckpointEngine):

    def __init__(self, config_params=None, async_save=False):
        super().__init__(config_params)
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self._async = async_save
        self._save_error = None  # failed save must never commit (nebula contract)
        self._ckptr = ocp.StandardCheckpointer() if not async_save else ocp.AsyncCheckpointer(
            ocp.StandardCheckpointHandler())

    def create(self, tag):
        logger.info(f"[OrbaxCheckpointEngine] Checkpoint {tag} is about to be saved!")

    def save(self, state_dict, path: str):
        """Arrays go to tensorstore; non-array client state to a pickle
        sidecar (host 0 only). In async mode this returns as soon as orbax
        has snapshotted the arrays — durability is only claimed by a later
        ``commit()`` returning True (the caller must NOT advertise the tag,
        e.g. via a ``latest`` write, on any other evidence)."""
        self._save_error = None
        arrays, meta = _split_state(state_dict)
        path = os.path.abspath(path)
        try:
            if arrays:
                self._ckptr.save(os.path.join(path, "arrays"), arrays, force=True)
                if not self._async:
                    # StandardCheckpointer finalizes in a background thread —
                    # a synchronous save contract must block here, else an
                    # immediate offline read sees arrays.orbax-checkpoint-tmp
                    self._ckptr.wait_until_finished()
            if jax.process_index() == 0:
                os.makedirs(path, exist_ok=True)
                with open(os.path.join(path, "meta.pkl"), "wb") as f:
                    pickle.dump(meta, f)
        except Exception as e:
            self._save_error = e
            raise
        return None

    def load(self, path: str, map_location=None, template=None):
        """``template`` is a pytree of jax.ShapeDtypeStruct with shardings —
        restore reshards to it (topology-change-tolerant load, the analog of
        the reference's elastic checkpoint load ``stage_1_and_2.py:2275``)."""
        path = os.path.abspath(path)
        meta_path = os.path.join(path, "meta.pkl")
        meta = {}
        if os.path.exists(meta_path):
            with open(meta_path, "rb") as f:
                meta = pickle.load(f)
        arrays = {}
        arrays_path = os.path.join(path, "arrays")
        expects_arrays = template is None or bool(_split_state(template)[0])
        if not os.path.exists(meta_path) and os.path.exists(arrays_path):
            # the inverse torn shape: save() always writes the meta sidecar
            # (even when empty), so arrays without it mean a crash between
            # the tensorstore finalize and the meta write. Loading it
            # silently hands back a tree with step counters/schedulers reset
            # to zero on old weights.
            raise CheckpointCorruptError(
                f"{path}: 'arrays' tree present but meta sidecar missing — partial "
                f"checkpoint (crash mid-write?); refusing to return a half-tree")
        if not os.path.exists(arrays_path):
            if expects_arrays and not meta:
                # neither payload half exists: a torn/never-committed dir (or
                # a bad path) — a silent empty merge here hands the caller a
                # half-tree that trains from garbage
                raise CheckpointCorruptError(f"{path}: no 'arrays' tree and no meta sidecar")
            if expects_arrays:
                raise CheckpointCorruptError(
                    f"{path}: 'arrays' tree missing but meta.pkl present — partial checkpoint "
                    f"(crash mid-write?); refusing to return a half-tree")
        else:
            try:
                if template is not None:
                    # partial restore against the on-disk metadata (a
                    # PyTreeRestore item must be the exact saved structure):
                    # template∩disk restores through the template's
                    # ShapeDtypeStructs (sharded placement), disk-only
                    # subtrees restore as host numpy, template-only subtrees
                    # come back as their ShapeDtypeStruct placeholders (the
                    # ``_fully_restored`` contract — e.g. a non-offload
                    # checkpoint loaded into an offload engine)
                    arr_template, _ = _split_state(template)
                    with self._ocp.Checkpointer(self._ocp.PyTreeCheckpointHandler()) as ckptr:
                        # StepMetadata -> TreeMetadata -> {key: subtree | ArrayMetadata}
                        saved = ckptr.metadata(arrays_path).item_metadata.tree
                        item, restore_args = self._merge_item(saved, arr_template)
                        arrays = ckptr.restore(
                            arrays_path,
                            args=self._ocp.args.PyTreeRestore(item=item, restore_args=restore_args))
                    arrays = _graft_missing(arrays, arr_template)
                else:
                    arrays = self._ckptr.restore(arrays_path)
            except CheckpointCorruptError:
                raise
            except Exception as e:
                # tensorstore surfaces torn shard files as a zoo of backend
                # errors; normalize so the fallback path has ONE type to catch
                raise CheckpointCorruptError(f"{arrays_path}: restore failed: {e}") from e
        return _merge_state(arrays, meta)

    def _merge_item(self, metadata, template):
        """Full-structure restore item + args: the saved tree's shape, with
        template leaves (and their shardings) where the template covers it."""
        item, args = {}, {}
        for k, mv in metadata.items():
            tv = template.get(k) if isinstance(template, dict) else None
            if isinstance(mv, dict):
                item[k], args[k] = self._merge_item(mv, tv if isinstance(tv, dict) else {})
            elif tv is not None and not isinstance(tv, dict):
                item[k] = tv
                args[k] = self._ocp.checkpoint_utils.construct_restore_args(tv)
            else:
                item[k] = jax.ShapeDtypeStruct(tuple(mv.shape), mv.dtype)
                args[k] = self._ocp.RestoreArgs(restore_type=np.ndarray, dtype=mv.dtype)
        return item, args

    def commit(self, tag):
        """True only when the tag is durably on disk. Async mode joins the
        background write here (decoupled from ``save``, so the step loop
        that called save already moved on); any recorded save failure makes
        this False — the caller keeps ``latest`` on the previous tag."""
        if self._async:
            try:
                self._ckptr.wait_until_finished()
            except Exception as e:
                self._save_error = self._save_error or e
        if self._save_error is not None:
            logger.error(f"[OrbaxCheckpointEngine] Checkpoint {tag} FAILED: {self._save_error!r}")
            return False
        logger.info(f"[OrbaxCheckpointEngine] Checkpoint {tag} is ready now!")
        return True


def _graft_missing(arrays, template):
    """Graft template-only subtrees (absent on disk) into the restored tree
    as their ShapeDtypeStruct placeholders."""
    if not isinstance(template, dict):
        return arrays
    out = dict(arrays) if isinstance(arrays, dict) else {}
    for k, tv in template.items():
        if k not in out:
            out[k] = tv
        elif isinstance(tv, dict) and isinstance(out[k], dict):
            out[k] = _graft_missing(out[k], tv)
    return out


def _is_array(x):
    return hasattr(x, "shape") and hasattr(x, "dtype")


def _split_state(state):
    """Partition a nested dict into (array leaves, other leaves)."""
    arrays, meta = {}, {}
    for k, v in state.items():
        if isinstance(v, dict):
            a, m = _split_state(v)
            if a:
                arrays[k] = a
            if m:
                meta[k] = m
        elif _is_array(v):
            arrays[k] = v
        else:
            meta[k] = v
    return arrays, meta


def _merge_state(arrays, meta):
    out = dict(meta) if isinstance(meta, dict) else {}
    for k, v in (arrays or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge_state(v, out[k])
        else:
            out[k] = v
    return out
