"""chip_smoke.py, rehearsed without the chip.

The script itself refuses anything but a TPU (pinned below). Its phase
functions take the run's size and the platform they should find, so
the same code that runs on the chip at AlexNet's full width runs here
at a tiny size on the CPU test mesh, with the Pallas kernels in the
interpret mode conftest.py chose: wrong paths, arguments, record names
and control flow are found here, at no chip time. That a kernel or a
step COMPILES for the chip is tests/test_chip_compile.py's job; that it
runs there is the chip run's.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke as cs

TINY = cs.Size(batch=8, image=67, src_image=72, n_images=32,
               dispatch_period=2, rounds=2, serve_buckets="2,4",
               serve_clients=2, serve_requests=4, serve_request_rows=2)

@pytest.fixture(scope="module")
def meter():
    return cs.CompileMeter()


@pytest.fixture(scope="module")
def cli(tmp_path_factory, meter):
    """Phases 1-2 (data, train) run once; pred and serve start from
    their snapshot, as in the script."""
    out = str(tmp_path_factory.mktemp("chip_smoke_out"))
    conf = cs.write_conf(out, TINY)
    data = cs.run_phase("data", lambda: cs.phase_data(out, TINY), meter)
    train = cs.run_phase("train", lambda: cs.phase_train(
        conf, out, TINY, "cpu", pallas_interpret=True), meter)
    return {"out": out, "conf": conf, "data": data, "train": train}


def test_data_and_train_phases(cli):
    assert cli["data"]["records"] == TINY.n_images
    assert cli["data"]["recordio"] in ("native", "python")
    tr = cli["train"]
    assert tr["ok"] and tr["platform"] == "cpu"
    # 32 images / batch 8 / window 2 = 2 dispatches a round, 2 rounds
    assert tr["steps"] == 4 and tr["examples"] == 64
    assert tr["precompile_programs"] > 0
    assert tr["compile_s"] > 0 and tr["wall_s"] >= tr["compile_s"]
    assert os.path.exists(tr["snapshot"])


def test_train_phase_fails_on_the_wrong_platform(cli):
    """No phase is wrapped in an except: a check that does not hold
    raises, and the script's exit code follows."""
    with pytest.raises(cs.SmokeFailure, match="platform 'cpu'"):
        cs.phase_pred(cli["conf"], cli["out"], TINY, "tpu",
                      cli["train"]["snapshot"])


def test_pred_phase(cli):
    line = cs.phase_pred(cli["conf"], cli["out"], TINY, "cpu",
                         cli["train"]["snapshot"])
    assert line["rows"] == TINY.n_images


def test_export_and_serve_from_bundle_phase(cli):
    line = cs.phase_serve(cli["conf"], cli["out"], TINY, "cpu",
                          cli["train"]["snapshot"])
    # buckets 2,4: full + padded variant each
    assert line["programs"] == line["artifact_hits"] == 4
    assert line["artifact_rebuilds"] == 0
    assert line["compile_events"] == 0
    assert line["requests"] == 8 and line["rows"] == 16


def test_data_parallel_phase_on_four_virtual_devices():
    """The --chips 4 path on four of the CPU test mesh's devices."""
    import jax
    line = cs.phase_data_parallel(jax.devices()[:4], batch=8, image=67,
                                  window=2, windows=2)
    one_dev, fused, zero1 = line["runs"]
    assert one_dev["mesh"]["data"] == 1 and not one_dev["all_reduce"]
    for r in (fused, zero1):
        assert r["mesh"]["data"] == 4 and r["all_reduce"]
        assert r["batch_shards"] == r["batch_devices"] == 4
        assert r["weight_replicas"] == 4
        assert r["max_rel_loss_diff"] <= line["loss_tol"]
    assert fused["momentum_shard_rows"] == fused["weight_shard_rows"]
    assert zero1["momentum_shard_rows"] * 4 == zero1["weight_shard_rows"]


# -- the script refuses anything but a TPU ------------------------------------


def _run_script(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script] + list(args),
                          capture_output=True, text=True, timeout=240,
                          env=env, cwd=os.path.dirname(script))


@pytest.mark.parametrize("args", [(), ("--chips", "4")])
def test_script_refuses_a_cpu(args):
    p = _run_script(os.path.join(REPO, "chip_smoke.py"), *args)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr
    assert p.stdout.strip() == ""          # no result line, no work


def test_script_refuses_without_the_repo(tmp_path):
    alone = str(tmp_path / "chip_smoke.py")
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), alone)
    p = _run_script(alone)
    assert p.returncode != 0
    assert "no cxxnet_tpu package" in p.stderr
    assert p.stdout.strip() == ""
