"""lm_train example: transformer pretraining over file-backed shards."""

import json
import types

import pytest


@pytest.mark.slow  # LM trainer end-to-end epochs
class TestLmTrain:
    def test_end_to_end_learns_and_logs(self, tmp_path):
        from edl_tpu.examples.lm_train import main

        rc = main(["--data-dir", str(tmp_path / "d"), "--make-synthetic",
                   "2", "--rows-per-file", "256", "--vocab", "128",
                   "--seq-len", "64", "--d-model", "64", "--n-heads", "4",
                   "--n-layers", "1", "--d-ff", "128", "--epochs", "4",
                   "--batch-size", "32", "--lr", "3e-3",
                   "--ckpt-dir", str(tmp_path / "ckpt"),
                   "--benchmark-log", str(tmp_path / "blog")])
        assert rc == 0
        blog = json.load(open(tmp_path / "blog" / "log_0.json"))
        # markov task: ideal loss ln(8)=2.08, chance ln(128)=4.85 — the
        # model must be clearly below chance after 4 tiny epochs
        assert blog["final"]["eval_loss"] < 4.2, blog["final"]
        assert blog["final"]["tokens_per_sec"] > 0

    def test_sequence_parallel_mesh(self, tmp_path):
        """--mesh sp: ring attention over the 8-device sequence axis
        through the CLI (long-context mode)."""
        from edl_tpu.examples.lm_train import main

        rc = main(["--data-dir", str(tmp_path / "d"), "--make-synthetic",
                   "1", "--rows-per-file", "64", "--vocab", "64",
                   "--seq-len", "64", "--d-model", "32", "--n-heads", "2",
                   "--n-layers", "1", "--d-ff", "64", "--epochs", "1",
                   "--batch-size", "16", "--mesh", "sp"])
        assert rc == 0

    def test_resume(self, tmp_path):
        from edl_tpu.examples.lm_train import main

        common = ["--data-dir", str(tmp_path / "d"), "--rows-per-file",
                  "128", "--vocab", "64", "--seq-len", "32", "--d-model",
                  "32", "--n-heads", "2", "--n-layers", "1", "--d-ff",
                  "64", "--batch-size", "16",
                  "--ckpt-dir", str(tmp_path / "ckpt")]
        assert main(["--make-synthetic", "1", "--epochs", "1"]
                    + common) == 0
        assert main(["--epochs", "2"] + common) == 0  # resumes epoch 1


# -- what the entry point refuses, each by the flag at fault ----------------

REFUSALS = [
    (["--schedule-epochs", "1", "--epochs", "2"], {}, "--schedule-epochs"),
    (["--moe", "--mesh", "fsdp"], {}, "--moe owns the ep mesh"),
    (["--moe", "--batch-size", "12"], {}, "--batch-size 12"),
    (["--mesh", "sp", "--seq-len", "12"], {}, "--seq-len 12"),
    (["--mesh", "sp"], {"world": 2}, "--mesh sp is single-process"),
    (["--dcn-compress", "int8", "--mesh", "fsdp"], {},
     "--dcn-compress/--comm-bucket-mb own the dp gradient"),
    (["--comm-bucket-mb", "1", "--mesh", "fsdp"], {},
     "--dcn-compress/--comm-bucket-mb own the dp gradient"),
    # the environment asks as the flag does, and a flag given beats it
    (["--mesh", "fsdp"], {"EDL_TPU_DCN_COMPRESS": "topk"},
     "--dcn-compress/--comm-bucket-mb own the dp gradient"),
    (["--moe", "--dcn-compress", "int8"], {}, "--moe-compress"),
    (["--moe", "--dcn-compress", "off", "--mesh", "fsdp"],
     {"EDL_TPU_DCN_COMPRESS": "topk"}, "--moe owns the ep mesh"),
    (["--arch", "granite-hybrid", "--moe"], {}, "--moe conflicts"),
    (["--arch", "granite-hybrid", "--n-layers", "2", "--layer-types",
      "mx"], {}, "--layer-types 'mx'"),
    (["--data-dir", "nowhere"], {}, "no train-*.npz"),
]


@pytest.fixture(scope="module")
def shard_dir(tmp_path_factory):
    from edl_tpu.examples.lm_train import make_synthetic_shards

    path = tmp_path_factory.mktemp("lm_refusals")
    make_synthetic_shards(str(path), 1, rows=16, seq_len=16, vocab=32)
    return path


@pytest.mark.parametrize("argv, setting, names", REFUSALS,
                         ids=[" ".join(r[0]) + "".join(r[1]) for r in REFUSALS])
def test_refusal_names_its_flag(shard_dir, tmp_path, monkeypatch, argv,
                                setting, names):
    from edl_tpu.examples import lm_train

    setting = dict(setting)
    world = setting.pop("world", 1)
    if world > 1:  # a process of a larger world, without joining one
        monkeypatch.setattr(
            lm_train.distributed, "init_from_env",
            lambda: types.SimpleNamespace(world_size=world, rank=0,
                                          checkpoint_path=None))
    for name, value in setting.items():
        monkeypatch.setenv(name, value)
    if "nowhere" in argv:
        (tmp_path / "nowhere").mkdir()
        argv = ["--data-dir", str(tmp_path / "nowhere")]
    else:
        argv = ["--data-dir", str(shard_dir)] + argv
    with pytest.raises(SystemExit) as refused:
        lm_train.main(["--seq-len", "16", "--vocab", "32", "--batch-size",
                       "16"] + argv)
    assert names in str(refused.value)
