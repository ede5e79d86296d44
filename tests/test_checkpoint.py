"""Iteration-state checkpoint/resume (utils/checkpoint.py): runtime
state persists beyond the reference's artifact-only resume point."""

import os

import numpy as np
import pytest

from arrow_matrix_tpu.decomposition import arrow_decomposition
from arrow_matrix_tpu.parallel import MultiLevelArrow, make_mesh
from arrow_matrix_tpu.utils import barabasi_albert, random_dense
from arrow_matrix_tpu.utils.checkpoint import load_state, save_state


@pytest.fixture()
def small(tmp_path):
    a = barabasi_albert(256, 4, seed=3)
    levels = arrow_decomposition(a, 32, max_levels=3, block_diagonal=True,
                                 seed=1)
    return a, levels, tmp_path


def test_checkpoint_roundtrip_sharded(small):
    _, levels, tmp = small
    ml = MultiLevelArrow(levels, 32, mesh=make_mesh((8,), ("blocks",)),
                         fmt="ell")
    x = ml.set_features(random_dense(256, 8, seed=2))
    x3 = ml.run(x, 3)
    save_state(str(tmp / "ck"), x3, 3)
    restored = load_state(str(tmp / "ck"), like=x)
    assert restored is not None
    xr, step = restored
    assert step == 3
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x3))
    assert xr.sharding == x.sharding     # restored sharded, not host


def test_checkpoint_roundtrip_fold(small):
    _, levels, tmp = small
    ml = MultiLevelArrow(levels, 32, mesh=None, fmt="fold")
    x = ml.set_features(random_dense(256, 8, seed=2))
    x2 = ml.run(x, 2)
    save_state(str(tmp / "ckf"), x2, 2)
    xr, step = load_state(str(tmp / "ckf"), like=x)
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x2))


def test_checkpoint_shape_mismatch_raises(small):
    _, levels, tmp = small
    ml = MultiLevelArrow(levels, 32, mesh=None, fmt="ell")
    x = ml.set_features(random_dense(256, 8, seed=2))
    save_state(str(tmp / "ckm"), x, 1)
    wrong = ml.set_features(random_dense(256, 4, seed=2))
    with pytest.raises(ValueError, match="shape"):
        load_state(str(tmp / "ckm"), like=wrong)


def test_load_state_absent_returns_none(tmp_path):
    assert load_state(str(tmp_path / "nope")) is None


def test_cli_carry_checkpoint_resume(tmp_path, monkeypatch):
    """CLI: a carried run checkpoints, and a rerun resumes mid-stream
    producing the same final state as one uninterrupted run."""
    from arrow_matrix_tpu.cli import spmm_arrow

    monkeypatch.chdir(tmp_path)
    common = ["--vertices", "300", "--width", "32", "--features", "4",
              "--device", "cpu", "--carry", "true",
              "--seed", "11", "--logdir", str(tmp_path / "logs")]
    # Uninterrupted 6-iteration run (no checkpoint interference).
    rc = spmm_arrow.main(common + ["--iterations", "6"])
    assert rc == 0
    # Run 4 iterations with checkpointing every 2, then resume to 6.
    ck = str(tmp_path / "ck")
    rc = spmm_arrow.main(common + ["--iterations", "4",
                                   "--checkpoint", ck,
                                   "--checkpoint_every", "2"])
    assert rc == 0
    rc = spmm_arrow.main(common + ["--iterations", "6",
                                   "--checkpoint", ck,
                                   "--checkpoint_every", "2",
                                   "--validate", "true"])
    assert rc == 0
    # The resumed run's final state must be bit-identical to an
    # uninterrupted checkpointing run of the same 6 iterations.
    ck2 = str(tmp_path / "ck2")
    rc = spmm_arrow.main(common + ["--iterations", "6",
                                   "--checkpoint", ck2,
                                   "--checkpoint_every", "2"])
    assert rc == 0
    xa, sa = load_state(ck)
    xb, sb = load_state(ck2)
    assert sa == sb == 6
    assert np.asarray(xa).tobytes() == np.asarray(xb).tobytes()


def test_cli_checkpoint_requires_carry(tmp_path, monkeypatch):
    from arrow_matrix_tpu.cli import spmm_arrow

    monkeypatch.chdir(tmp_path)
    with pytest.raises(SystemExit, match="carry"):
        spmm_arrow.main(["--vertices", "200", "--width", "32",
                         "--device", "cpu",
                         "--checkpoint", str(tmp_path / "x")])


def test_checkpoint_roundtrip_sell_multilevel(small):
    """Feature-major sharded carriage (SellMultiLevel) through the
    checkpoint: restore lands on the executor's sharding."""
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel

    _, levels, tmp = small
    sm = SellMultiLevel(levels, 32, make_mesh((8,), ("blocks",)))
    x = sm.set_features(random_dense(256, 8, seed=2))
    x2 = sm.run(x, 2)
    save_state(str(tmp / "cks"), x2, 2)
    xr, step = load_state(str(tmp / "cks"), like=x)
    assert step == 2
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x2))
    assert xr.sharding == x.sharding


def test_checkpoint_roundtrip_sell_space_shared(small):
    """The concurrent-group carriage (K carried orderings on the 2-D
    (lvl, blocks) mesh) through the checkpoint."""
    from arrow_matrix_tpu.parallel import SellSpaceShared

    _, levels, tmp = small
    if len(levels) < 2:
        pytest.skip("need >=2 levels for a lvl axis")
    sp = SellSpaceShared(levels[:2], 32,
                         make_mesh((2, 4), ("lvl", "blocks")))
    x = sp.set_features(random_dense(256, 8, seed=2))
    x2 = sp.run(x, 2)
    save_state(str(tmp / "cksp"), x2, 2)
    xr, step = load_state(str(tmp / "cksp"), like=x)
    assert step == 2
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x2))
    assert xr.sharding == x.sharding


def test_checkpoint_layout_mismatch_raises(tmp_path):
    """A checkpoint tagged with one carriage layout must refuse to
    resume under another — silently permuted rows are worse than a
    crash."""
    x = np.arange(12, dtype=np.float32).reshape(4, 3)
    save_state(str(tmp_path / "ckl"), x, 2, layout="fold/ell/f32")
    with pytest.raises(RuntimeError, match="layout"):
        load_state(str(tmp_path / "ckl"), layout="sell/slim/f32")
    # matching layout (and layout-agnostic load) both succeed
    xr, step = load_state(str(tmp_path / "ckl"), layout="fold/ell/f32")
    assert step == 2
    xr, step = load_state(str(tmp_path / "ckl"))
    np.testing.assert_array_equal(np.asarray(xr), x)


def test_checkpoint_layout_mismatch_npz_fallback(tmp_path, monkeypatch):
    """Same layout guard on the npz fallback path (no orbax)."""
    from arrow_matrix_tpu.utils import checkpoint as ckpt

    monkeypatch.setattr(ckpt, "_orbax", lambda: None)
    x = np.arange(12, dtype=np.float32).reshape(4, 3)
    ckpt.save_state(str(tmp_path / "ckn"), x, 3, layout="petsc/1d_sliced")
    with pytest.raises(RuntimeError, match="layout"):
        ckpt.load_state(str(tmp_path / "ckn"), layout="15d/c2")
    xr, step = ckpt.load_state(str(tmp_path / "ckn"),
                               layout="petsc/1d_sliced")
    assert step == 3
    np.testing.assert_array_equal(np.asarray(xr), x)


def test_checkpoint_untagged_legacy_npz_tolerated(tmp_path, monkeypatch):
    """A pre-versioning npz checkpoint (no version/layout fields) still
    loads; a checkpoint from a NEWER format version fails loudly."""
    from arrow_matrix_tpu.utils import checkpoint as ckpt

    monkeypatch.setattr(ckpt, "_orbax", lambda: None)
    x = np.ones((4, 2), dtype=np.float32)
    np.savez(str(tmp_path / "legacy.npz"), x=x, step=np.int64(5))
    xr, step = ckpt.load_state(str(tmp_path / "legacy"),
                               layout="fold/ell/f32")
    assert step == 5
    np.savez(str(tmp_path / "future.npz"), x=x, step=np.int64(5),
             version=np.int64(ckpt.CHECKPOINT_VERSION + 1),
             layout=np.str_(""))
    with pytest.raises(RuntimeError, match="newer"):
        ckpt.load_state(str(tmp_path / "future"))


def test_load_state_emits_resumed_flight_event(tmp_path):
    from arrow_matrix_tpu.obs import flight

    x = np.ones((3, 2), dtype=np.float32)
    save_state(str(tmp_path / "ckev"), x, 7, layout="t")
    rec = flight.FlightRecorder(str(tmp_path / "flight.json"))
    old = flight.get_recorder()
    flight.set_recorder(rec)
    try:
        load_state(str(tmp_path / "ckev"))
    finally:
        flight.set_recorder(old)
    ev = [e for e in rec.events if e.get("name") == "resumed"]
    assert ev and ev[0]["kind"] == "heal"
    assert ev[0]["data"]["step"] == 7


def test_interrupted_save_keeps_the_previous_checkpoint(tmp_path):
    """A save cut between its two renames leaves the previous state as
    ``.prev``: load falls back to it, and listings skip the transient
    names (a kill mid-save must not lose the last good checkpoint)."""
    import jax.numpy as jnp

    from arrow_matrix_tpu.utils.checkpoint import list_checkpoints

    path = str(tmp_path / "ck_r1")
    x = jnp.arange(8, dtype=jnp.float32).reshape(2, 4)
    save_state(path, x, 3, layout="t")
    save_state(path, x * 2, 4, layout="t")
    got, step = load_state(path, layout="t")
    assert step == 4
    np.testing.assert_array_equal(np.asarray(got), 2 * np.asarray(x))
    if not os.path.isdir(path):
        pytest.skip("npz backend: the save is a single atomic replace")
    os.rename(path, path + ".prev")          # cut after the first rename
    os.makedirs(path + ".saving")            # the new save, unfinished
    got, step = load_state(path)
    assert step == 4
    assert list_checkpoints(str(tmp_path)) == []
