import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager, load_checkpoint, save_checkpoint
from repro.ckpt import checkpoint as ckpt_mod
from repro.ckpt.checkpoint import latest_step


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": {"w": jax.random.normal(k, (8, 16)),
                  "b": jnp.zeros((16,), jnp.bfloat16)},
            "step": jnp.asarray(7, jnp.int32)}


def test_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 5, t)
    restored, step = load_checkpoint(str(tmp_path), t)
    assert step == 5
    np.testing.assert_allclose(np.asarray(restored["a"]["w"]),
                               np.asarray(t["a"]["w"]))
    assert restored["a"]["b"].dtype == jnp.bfloat16


def test_roundtrip_zlib_fallback_codec(tmp_path, monkeypatch):
    """A checkpoint written on a minimal install (no zstandard) must
    round-trip through the stdlib zlib codec, and the manifest must say
    so — a zstd reader is never required to restore it."""
    monkeypatch.setattr(ckpt_mod, "_zstd", None)
    monkeypatch.setattr(ckpt_mod, "_CODEC", "zlib")
    t = _tree(1)
    d = save_checkpoint(str(tmp_path), 9, t)
    import msgpack
    with open(os.path.join(d, "manifest.msgpack"), "rb") as f:
        assert msgpack.unpackb(f.read())["codec"] == "zlib"
    restored, step = load_checkpoint(str(tmp_path), t)
    assert step == 9
    np.testing.assert_allclose(np.asarray(restored["a"]["w"]),
                               np.asarray(t["a"]["w"]))
    assert restored["a"]["b"].dtype == jnp.bfloat16


def test_codec_error_paths(monkeypatch):
    with pytest.raises(ValueError, match="unknown checkpoint codec"):
        ckpt_mod._decompress(b"x", "lz4")
    monkeypatch.setattr(ckpt_mod, "_zstd", None)
    with pytest.raises(RuntimeError, match="compress"):
        ckpt_mod._decompress(b"x", "zstd")


def test_atomicity_tmp_cleanup(tmp_path):
    t = _tree()
    final = save_checkpoint(str(tmp_path), 1, t)
    assert final.endswith("step_00000001")
    assert latest_step(str(tmp_path)) == 1
    # a second save at a new step becomes latest
    save_checkpoint(str(tmp_path), 2, t)
    assert latest_step(str(tmp_path)) == 2


def test_manager_interval_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), interval=2, keep=2)
    t = _tree()
    for step in range(1, 9):
        mgr.maybe_save(step, t)
    mgr.wait()
    assert latest_step(str(tmp_path)) == 8
    import os
    kept = [d for d in os.listdir(tmp_path) if d.startswith("step_")]
    assert len(kept) <= 2


def test_manager_gc_keeps_exactly_newest(tmp_path):
    """Retention is exact: keep=3 leaves precisely the three newest step
    directories, and restore reads the newest survivor."""
    mgr = CheckpointManager(str(tmp_path), interval=1, keep=3)
    t = _tree()
    for step in range(1, 7):
        mgr.maybe_save(step, t)
    mgr.wait()
    kept = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                  if d.startswith("step_") and not d.endswith(".tmp"))
    assert kept == [4, 5, 6]
    _, step = mgr.restore(t)
    assert step == 6


def test_manager_restore(tmp_path):
    mgr = CheckpointManager(str(tmp_path), interval=1)
    t = _tree(3)
    mgr.maybe_save(4, t)
    mgr.wait()
    restored, step = mgr.restore(t)
    assert step == 4
    np.testing.assert_allclose(np.asarray(restored["a"]["w"]),
                               np.asarray(t["a"]["w"]))


def test_elastic_reshard_subprocess(tmp_path, request):
    """Save on 1 device, restore onto an 8-device (4,2) mesh with sharding."""
    t = _tree()
    save_checkpoint(str(tmp_path), 3, t)
    from conftest import run_py
    code = f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as PS
from repro.ckpt import load_checkpoint
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
target = {{"a": {{"w": jax.ShapeDtypeStruct((8, 16), jnp.float32),
               "b": jax.ShapeDtypeStruct((16,), jnp.bfloat16)}},
          "step": jax.ShapeDtypeStruct((), jnp.int32)}}
specs = {{"a": {{"w": PS("data", "model"), "b": PS()}}, "step": PS()}}
tree, step = load_checkpoint({str(tmp_path)!r}, target, mesh=mesh,
                             spec_tree=specs)
assert step == 3
assert len(tree["a"]["w"].sharding.device_set) == 8
print("reshard-ok", float(jnp.sum(tree["a"]["w"])))
"""
    out = run_py(code, devices=8)
    assert "reshard-ok" in out
