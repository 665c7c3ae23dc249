"""Checkpoint atomicity / resume / retention / async."""
import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as ckpt
from repro.train.elastic import resume_or_init


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32),
        "nested": {"b": jnp.asarray(rng.integers(0, 9, (2,)), jnp.int32)},
    }


def _assert_tree_equal(x, y):
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        x, y)


def test_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(tmp_path, 3, t, extra={"data_step": 4})
    got, extra, step = ckpt.restore(tmp_path, jax.eval_shape(lambda: t))
    assert step == 3 and extra == {"data_step": 4}
    _assert_tree_equal(t, got)


def test_partial_write_is_ignored(tmp_path):
    t = _tree()
    ckpt.save(tmp_path, 1, t)
    # forge a later, uncommitted (crashed) checkpoint
    bad = tmp_path / "step_00000002"
    bad.mkdir()
    (bad / "manifest.json").write_text("{}")
    assert ckpt.latest_step(tmp_path) == 1
    got, _, step = ckpt.restore(tmp_path, jax.eval_shape(lambda: t))
    assert step == 1
    _assert_tree_equal(t, got)


def _crash_save(root, step, tree, *, crash_after):
    """Replay `ckpt.save`'s write sequence and die at a chosen point.

    crash_after="tmp": after the tmp-dir write, before the rename (the
    classic kill-mid-save window); crash_after="rename": after the rename
    but before COMMIT (the narrower window the COMMIT file closes).
    """
    root = Path(root)
    tmp = root / f"step_{step:08d}.tmp"
    tmp.mkdir(parents=True)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    for i, leaf in enumerate(leaves):
        np.save(tmp / f"arr_{i:05d}.npy", np.asarray(leaf))
    (tmp / "manifest.json").write_text(json.dumps({
        "step": step, "treedef": str(treedef), "n_leaves": len(leaves),
        "extra": {}, "leaves": [
            {"shape": list(np.asarray(x).shape),
             "dtype": str(np.asarray(x).dtype)} for x in leaves]}))
    if crash_after == "tmp":
        return tmp
    final = root / f"step_{step:08d}"
    tmp.rename(final)
    return final  # crashed before COMMIT


def test_crash_between_tmp_write_and_commit(tmp_path):
    """A kill anywhere in the save window never corrupts the last COMMIT:
    both crash points fall back to the previous committed step, and the
    abandoned tmp dir is swept by the next successful save."""
    t = _tree()
    ckpt.save(tmp_path, 1, t, extra={"cursor": 11})

    # crash point A: tmp fully written, rename never happened
    junk_tmp = _crash_save(tmp_path, 2, _tree(9), crash_after="tmp")
    # crash point B: renamed into place, COMMIT never written
    _crash_save(tmp_path, 3, _tree(9), crash_after="rename")

    assert ckpt.latest_step(tmp_path) == 1
    got, extra, step = ckpt.restore(tmp_path, jax.eval_shape(lambda: t))
    assert step == 1 and extra == {"cursor": 11}
    _assert_tree_equal(t, got)

    # the junk tmp dir is pruned by the next save once it is stale
    # (age-guarded so a live concurrent save_async writer is never raced)
    old = time.time() - 3600
    os.utime(junk_tmp, (old, old))
    ckpt.save(tmp_path, 4, t)
    assert not junk_tmp.exists()
    assert ckpt.latest_step(tmp_path) == 4


def test_fresh_tmp_dir_survives_sweep(tmp_path):
    """A tmp dir younger than the staleness window is left alone."""
    t = _tree()
    live_tmp = _crash_save(tmp_path, 7, t, crash_after="tmp")
    ckpt.save(tmp_path, 8, t)
    assert live_tmp.exists()


def test_extra_validation():
    assert ckpt.validate_extra(None) == {}
    # normalization happens before the write: tuples come back as lists
    assert ckpt.validate_extra({"cursor": (1, 2)}) == {"cursor": [1, 2]}
    with pytest.raises(TypeError, match="extra\\['bad'\\]"):
        ckpt.validate_extra({"bad": np.zeros(3)})
    with pytest.raises(TypeError, match="dict"):
        ckpt.validate_extra([1, 2])


def test_save_rejects_bad_extra_before_writing(tmp_path):
    with pytest.raises(TypeError):
        ckpt.save(tmp_path, 0, _tree(), extra={"arr": np.zeros(2)})
    assert list(tmp_path.glob("step_*")) == []  # fail-fast: nothing on disk


def test_retention(tmp_path):
    t = _tree()
    for s in range(6):
        ckpt.save(tmp_path, s, t, keep_last=2)
    committed = sorted(p.name for p in tmp_path.glob("step_*")
                       if (p / "COMMIT").exists())
    assert committed == ["step_00000004", "step_00000005"]


def test_async_save(tmp_path):
    t = _tree()
    th = ckpt.save_async(tmp_path, 7, t)
    th.join()
    got, _, step = ckpt.restore(tmp_path, jax.eval_shape(lambda: t))
    assert step == 7
    _assert_tree_equal(t, got)


def test_resume_or_init(tmp_path):
    t = _tree(5)
    abstract = jax.eval_shape(lambda: t)
    got, extra, start = resume_or_init(tmp_path, lambda: t, abstract)
    assert start == 0
    ckpt.save(tmp_path, 9, t, extra={"data_step": 10})
    got, extra, start = resume_or_init(tmp_path, lambda: _tree(1), abstract)
    assert start == 10
    _assert_tree_equal(t, got)


def test_shape_mismatch_raises(tmp_path):
    ckpt.save(tmp_path, 0, _tree())
    wrong = {"a": jax.ShapeDtypeStruct((5, 3), jnp.float32),
             "nested": {"b": jax.ShapeDtypeStruct((2,), jnp.int32)}}
    with pytest.raises(ValueError):
        ckpt.restore(tmp_path, wrong)


def test_crc_mismatch_raises_corrupt_error(tmp_path):
    """Silent bit-rot in a committed array is caught by the per-leaf CRC."""
    t = _tree()
    final = ckpt.save(tmp_path, 0, t)
    f = sorted(final.glob("arr_*.npy"))[0]
    data = bytearray(f.read_bytes())
    data[-1] ^= 0x01  # payload byte, past the .npy header
    f.write_bytes(bytes(data))
    with pytest.raises(ckpt.CorruptCheckpointError, match="CRC mismatch"):
        ckpt.restore(tmp_path, jax.eval_shape(lambda: t))
    # verify=False restores the (corrupt) bytes without complaint — the
    # chain-walking caller decides, not the primitive
    got, _, step = ckpt.restore(tmp_path, jax.eval_shape(lambda: t),
                                verify=False)
    assert step == 0


def test_v1_manifest_restores_unverified(tmp_path):
    """Pre-CRC (format v1) checkpoints still restore — back-compat."""
    t = _tree()
    final = ckpt.save(tmp_path, 0, t)
    mpath = final / "manifest.json"
    m = json.loads(mpath.read_text())
    m.pop("format_version", None)
    for leaf in m["leaves"]:
        leaf.pop("crc32", None)
    mpath.write_text(json.dumps(m))
    got, _, step = ckpt.restore(tmp_path, jax.eval_shape(lambda: t))
    assert step == 0
    _assert_tree_equal(t, got)


def test_unreadable_manifest_raises_corrupt_error(tmp_path):
    t = _tree()
    final = ckpt.save(tmp_path, 0, t)
    (final / "manifest.json").write_text('{"half": tru')
    with pytest.raises(ckpt.CorruptCheckpointError, match="manifest"):
        ckpt.restore(tmp_path, jax.eval_shape(lambda: t))


def test_async_save_error_surfaces(tmp_path):
    """Regression: a failing background save must NOT die silently with
    its daemon thread — the error re-raises from `wait_pending` and from
    the next save call."""
    (tmp_path / "blocker").write_text("i am a file, not a directory")
    bad_root = tmp_path / "blocker" / "ckpt"  # mkdir → ENOTDIR, as root too
    t = _tree()
    th = ckpt.save_async(bad_root, 0, t)
    th.join()
    with pytest.raises(OSError):
        ckpt.wait_pending()
    # drained: a subsequent healthy save is clean
    assert ckpt.wait_pending() == []
    ckpt.save(tmp_path / "ok", 1, t)

    # the same failure also surfaces at the *next* save call, for callers
    # that never explicitly drain
    ckpt.save_async(bad_root, 2, t).join()
    with pytest.raises(OSError):
        ckpt.save(tmp_path / "ok", 3, t)
    assert ckpt.wait_pending(raise_errors=False) == []


def test_wait_pending_collects_without_raising(tmp_path):
    (tmp_path / "blocker").write_text("x")
    bad_root = tmp_path / "blocker" / "ckpt"
    ckpt.save_async(bad_root, 0, _tree()).join()
    errs = ckpt.wait_pending(raise_errors=False)
    assert len(errs) == 1 and isinstance(errs[0], OSError)


def test_transient_io_error_is_retried(tmp_path):
    """Injected EIO on the first two attempts: the third succeeds, and each
    retry lands in RunHealth."""
    from repro.core import RunHealth
    from repro.runtime import FaultPlan, FaultSpec, faults

    t = _tree()
    health = RunHealth()
    faults.install(FaultPlan(
        [FaultSpec("save.io", "io_error", at=1, times=2, errno_name="EIO")]))
    try:
        ckpt.save(tmp_path, 0, t, retries=2, retry_backoff_s=0.0,
                  health=health)
    finally:
        faults.clear()
    assert ckpt.latest_step(tmp_path) == 0
    assert health.count("save_retry") == 2

    # beyond the retry budget the error propagates (it is not transient
    # forever) — and a *non*-transient errno never retries at all
    faults.install(FaultPlan(
        [FaultSpec("save.io", "io_error", at=1, times=99,
                   errno_name="ENOSPC")]))
    try:
        with pytest.raises(OSError):
            ckpt.save(tmp_path, 1, t, retries=2, retry_backoff_s=0.0)
    finally:
        faults.clear()
    faults.install(FaultPlan(
        [FaultSpec("save.io", "io_error", at=1, errno_name="EACCES")]))
    try:
        health2 = RunHealth()
        with pytest.raises(PermissionError):
            ckpt.save(tmp_path, 2, t, retries=2, retry_backoff_s=0.0,
                      health=health2)
        assert health2.count("save_retry") == 0
    finally:
        faults.clear()


def test_stale_ttl_configurable(tmp_path, monkeypatch):
    """The abandoned-tmp sweep TTL comes from the arg, then the env var,
    then the 60s default."""
    t = _tree()
    junk = _crash_save(tmp_path, 5, t, crash_after="tmp")
    old = time.time() - 10
    os.utime(junk, (old, old))
    # default TTL (60s): a 10s-old tmp survives
    ckpt.save(tmp_path, 6, t)
    assert junk.exists()
    # per-call override: now it is stale
    ckpt.save(tmp_path, 7, t, stale_tmp_s=5.0)
    assert not junk.exists()
    # env override works the same way
    junk2 = _crash_save(tmp_path, 8, t, crash_after="tmp")
    os.utime(junk2, (old, old))
    monkeypatch.setenv(ckpt.STALE_TMP_ENV, "5")
    ckpt.save(tmp_path, 9, t)
    assert not junk2.exists()


def test_sweep_never_touches_this_process_live_tmp(tmp_path):
    """A tmp dir registered as in-flight by this process is excluded from
    the sweep even when it looks ancient — an aggressive TTL can never
    race a live `save_async` writer."""
    t = _tree()
    live = _crash_save(tmp_path, 5, t, crash_after="tmp")
    old = time.time() - 3600
    os.utime(live, (old, old))
    with ckpt._ACTIVE_LOCK:
        ckpt._ACTIVE_TMP.add(live)
    try:
        ckpt.save(tmp_path, 6, t, stale_tmp_s=0.0)
        assert live.exists()
    finally:
        with ckpt._ACTIVE_LOCK:
            ckpt._ACTIVE_TMP.discard(live)
    # deregistered (writer finished/died), same TTL: now it is swept
    ckpt.save(tmp_path, 7, t, stale_tmp_s=0.0)
    assert not live.exists()


def test_committed_steps_ascending(tmp_path):
    t = _tree()
    for s in (4, 1, 9):
        ckpt.save(tmp_path, s, t, keep_last=10)
    _crash_save(tmp_path, 12, t, crash_after="rename")  # no COMMIT
    assert ckpt.committed_steps(tmp_path) == [1, 4, 9]


def test_restore_with_shardings(tmp_path):
    """Elastic path: restore re-shards (trivially, on 1 device)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    t = _tree()
    ckpt.save(tmp_path, 0, t)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    sh = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), jax.eval_shape(lambda: t))
    got, _, _ = ckpt.restore(tmp_path, jax.eval_shape(lambda: t),
                             shardings=sh)
    _assert_tree_equal(t, got)
