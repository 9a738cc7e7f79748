"""Checkpoint durability: atomic commit, corrupt-file handling, resume
entry point.

The failure being engineered away: a crash mid-`np.savez` used to leave a
`pass-%05d/model.npz` that LOOKS loadable (the dir exists, the file
exists) but dies inside zipfile at load time — the worst possible resume
experience.  Saves now stage the whole pass dir under `.tmp` and rename
into place last, so every committed dir is complete by construction and
every reader skips stragglers."""

import os

import numpy as np
import pytest

from paddle_tpu.trainer import checkpoint as ckpt


def _params(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": rng.normal(size=(4, 3)).astype(np.float32),
            "b": rng.normal(size=(3,)).astype(np.float32)}


def test_save_commits_atomically_and_roundtrips(tmp_path):
    save_dir = str(tmp_path / "ck")
    params = _params()
    d = ckpt.save_checkpoint(save_dir, 0, params, config_json='{"a": 1}')
    assert os.path.basename(d) == "pass-00000"
    # no staging residue once committed
    assert not any(x.endswith(".tmp") or x.endswith(".part")
                   for x in os.listdir(save_dir))
    assert not any(x.endswith(".part") for x in os.listdir(d))
    out = ckpt.load_checkpoint(d)
    np.testing.assert_array_equal(out["params"]["w"], params["w"])
    assert out["config_json"] == '{"a": 1}'
    # re-saving the same pass replaces it cleanly
    params2 = _params(seed=1)
    ckpt.save_checkpoint(save_dir, 0, params2)
    out2 = ckpt.load_checkpoint(d)
    np.testing.assert_array_equal(out2["params"]["w"], params2["w"])


def test_stale_tmp_straggler_is_invisible_and_overwritten(tmp_path):
    """A crash between staging and rename leaves `pass-%05d.tmp` — every
    reader must skip it, and the next save of that pass must clobber it."""
    save_dir = str(tmp_path / "ck")
    ckpt.save_checkpoint(save_dir, 0, _params())
    straggler = os.path.join(save_dir, "pass-00001.tmp")
    os.makedirs(straggler)
    with open(os.path.join(straggler, "model.npz"), "wb") as f:
        f.write(b"half a zip")
    assert ckpt.latest_pass(save_dir) == 0
    assert ckpt.latest_checkpoint(save_dir).endswith("pass-00000")
    # resume-from-root keeps working (load_checkpoint ignores the .tmp)
    out = ckpt.load_checkpoint(save_dir)
    assert out["pass_id"] == 0
    # saving pass 1 for real sweeps the straggler and commits
    d = ckpt.save_checkpoint(save_dir, 1, _params(seed=2))
    assert not os.path.isdir(straggler)
    assert ckpt.latest_checkpoint(save_dir) == d


def test_corrupt_npz_raises_actionable_error(tmp_path):
    """A truncated model.npz must name the offending path, not surface a
    raw zipfile.BadZipFile from the guts of numpy."""
    save_dir = str(tmp_path / "ck")
    d = ckpt.save_checkpoint(save_dir, 0, _params())
    npz = os.path.join(d, "model.npz")
    blob = open(npz, "rb").read()
    with open(npz, "wb") as f:
        f.write(blob[: len(blob) // 2])            # torn write
    with pytest.raises(ValueError, match="corrupt or truncated") as ei:
        ckpt.load_checkpoint(d)
    assert npz in str(ei.value)


def test_latest_checkpoint_resume_entry_point(tmp_path):
    save_dir = str(tmp_path / "ck")
    assert ckpt.latest_checkpoint(save_dir) is None
    ckpt.save_checkpoint(save_dir, -1, _params())     # pre-training snap
    assert ckpt.latest_checkpoint(save_dir).endswith("pass-init")
    ckpt.save_checkpoint(save_dir, 0, _params())
    ckpt.save_checkpoint(save_dir, 3, _params())
    assert ckpt.latest_checkpoint(save_dir).endswith("pass-00003")


def test_keep_last_prunes_only_after_commit(tmp_path):
    save_dir = str(tmp_path / "ck")
    for p in range(4):
        ckpt.save_checkpoint(save_dir, p, _params(seed=p), keep_last=2)
    kept = sorted(x for x in os.listdir(save_dir))
    assert kept == ["pass-00002", "pass-00003"]
    # the survivor of the pruning is the newly COMMITTED dir — loadable
    out = ckpt.load_checkpoint(save_dir)
    assert out["pass_id"] == 3
    # an orphaned straggler from a crashed save of ANOTHER pass (never
    # re-saved, so same-pass cleanup never sees it) is swept by pruning
    os.makedirs(os.path.join(save_dir, "pass-00009.tmp"))
    ckpt.save_checkpoint(save_dir, 4, _params(), keep_last=2)
    assert not os.path.isdir(os.path.join(save_dir, "pass-00009.tmp"))
    assert sorted(os.listdir(save_dir)) == ["pass-00003", "pass-00004"]


def test_load_canonicalizes_key_order(tmp_path):
    """load_checkpoint must return identically-ORDERED trees no matter
    what order the writer inserted npz entries in — the trainer's save()
    flattens jax-pytree-sorted, but the pserver's streaming snapshotter
    assembles blocks in its own iteration order, and optimizer-slot
    iteration order must round-trip deterministically either way."""
    d = tmp_path / "pass-00000"
    d.mkdir(parents=True)
    sep = ckpt.SEP
    arrs = {
        f"params{sep}w": np.arange(6, dtype=np.float32),
        f"params{sep}b": np.ones(3, np.float32),
        f"opt{sep}slots{sep}w{sep}momentum": np.zeros(6, np.float32),
        f"opt{sep}slots{sep}b{sep}momentum": np.zeros(3, np.float32),
        f"opt{sep}slots{sep}a{sep}momentum": np.zeros(2, np.float32),
        f"opt{sep}num_updates": np.int32(4),
    }
    # adversarial writer: reverse-sorted insertion (npz preserves order)
    with open(d / "model.npz", "wb") as f:
        np.savez(f, **{k: arrs[k] for k in sorted(arrs, reverse=True)})
    out = ckpt.load_checkpoint(str(d))
    assert list(out["params"]) == ["b", "w"]
    assert list(out["opt"]["slots"]) == ["a", "b", "w"]
    # and a canonical writer produces the very same ordering
    d2 = ckpt.save_checkpoint(
        str(tmp_path / "ck2"), 0,
        {"w": arrs[f"params{sep}w"], "b": arrs[f"params{sep}b"]},
        opt_state={"slots": {"w": {"momentum": np.zeros(6, np.float32)},
                             "b": {"momentum": np.zeros(3, np.float32)},
                             "a": {"momentum": np.zeros(2, np.float32)}},
                   "num_updates": np.int32(4)})
    out2 = ckpt.load_checkpoint(d2)
    assert list(out2["params"]) == list(out["params"])
    assert list(out2["opt"]["slots"]) == list(out["opt"]["slots"])
    for name in out["opt"]["slots"]:
        assert list(out2["opt"]["slots"][name]) == \
            list(out["opt"]["slots"][name])


def test_train_saving_period_skips_passes_but_keeps_last_and_metrics(tmp_path):
    """--saving_period=N: a checkpoint every N passes and after the last
    pass; the metrics row still lands every pass."""
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.trainer.trainer import Trainer

    cfg = parse_config("demo/model_zoo/transformer_lm.py",
                       "vocab=32,dim=16,layers=1,heads=2,batch_size=64")
    save_dir = str(tmp_path / "run")
    Trainer(cfg, seed=1).train(num_passes=3, log_period=0, save_dir=save_dir,
                               saving_period=2)
    passes = sorted(d for d in os.listdir(save_dir) if d.startswith("pass-"))
    assert passes == ["pass-00001", "pass-00002"]
    with open(os.path.join(save_dir, "metrics.jsonl")) as f:
        assert len(f.readlines()) == 3
