"""Top-level paddle package parity: compat, utils (Ploter/image_util),
distributed launchers, proto shim (reference python/paddle/{compat,utils,
distributed,proto}).  The launcher tests spawn real subprocesses and
assert the PADDLE_* env contract reaches the children."""

import json
import os
import pickle
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu import compat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- compat ---------------------------------------------------------------

def test_compat_text_bytes():
    assert compat.to_text(b"abc") == "abc"
    assert compat.to_bytes("abc") == b"abc"
    assert compat.to_text([b"a", "b"]) == ["a", "b"]
    assert compat.to_bytes({"a"}) == {b"a"}
    lst = [b"x", b"y"]
    assert compat.to_text(lst, inplace=True) is lst and lst == ["x", "y"]


def test_compat_round_is_py2_style():
    assert compat.round(0.5) == 1.0      # py3 builtin gives 0
    assert compat.round(-0.5) == -1.0    # py3 builtin gives -0
    assert compat.round(2.675, 2) == 2.68
    assert compat.round(0.0) == 0.0
    assert compat.floor_division(7, 2) == 3
    assert compat.long_type is int
    assert compat.get_exception_message(ValueError("boom")) == "boom"


# --- utils.plot -----------------------------------------------------------

def test_ploter_saves_figure(tmp_path):
    from paddle_tpu.utils import Ploter
    p = Ploter("train", "test")
    for i in range(5):
        p.append("train", i, 1.0 / (i + 1))
        p.append("test", i, 1.2 / (i + 1))
    out = tmp_path / "curve.png"
    p.plot(str(out))
    assert out.exists() and out.stat().st_size > 0
    with pytest.raises(AssertionError):
        p.append("nope", 0, 0.0)
    p.reset()
    assert not p.__plot_data__["train"].step


def test_ploter_disabled_is_inert(monkeypatch):
    monkeypatch.setenv("DISABLE_PLOT", "True")
    from paddle_tpu.utils.plot import Ploter
    p = Ploter("x")
    p.append("x", 0, 1.0)
    p.plot("/nonexistent/dir/never_written.png")  # no-op when disabled


# --- utils.image_util -----------------------------------------------------

def test_image_util_crop_and_flip():
    from paddle_tpu.utils import image_util
    im = np.arange(3 * 12 * 12, dtype=np.float32).reshape(3, 12, 12)
    center = image_util.crop_img(im, 8, color=True, test=True)
    np.testing.assert_array_equal(center, im[:, 2:10, 2:10])
    # smaller than crop: zero-padded up
    small = image_util.crop_img(im[:, :4, :4], 8, color=True, test=True)
    assert small.shape == (3, 8, 8)
    assert small.sum() == im[:, :4, :4].sum()
    gray = image_util.crop_img(np.ones((12, 12)), 8, color=False, test=True)
    assert gray.shape == (8, 8)
    np.testing.assert_array_equal(image_util.flip(im), im[:, :, ::-1])


def test_image_util_preprocess_and_meta(tmp_path):
    from paddle_tpu.utils import image_util
    im = np.random.RandomState(0).rand(3, 16, 16).astype("float32")
    flat = image_util.preprocess_img(im, img_mean=0.5, crop_size=8,
                                     is_train=False)
    assert flat.shape == (3 * 8 * 8,)
    mean = np.random.RandomState(1).rand(3 * 16 * 16).astype("float32")
    meta = tmp_path / "mean.pkl"
    meta.write_bytes(pickle.dumps(mean))
    loaded = image_util.load_meta(str(meta), 16, 8, color=True)
    assert loaded.shape == (3, 8, 8)


def test_image_util_oversample():
    from paddle_tpu.utils import image_util
    imgs = [np.random.RandomState(i).rand(12, 12, 3) for i in range(2)]
    crops = image_util.oversample(imgs, (8, 8))
    assert crops.shape == (20, 8, 8, 3)
    # 10th crop of each image is a mirror of one of the first five
    np.testing.assert_allclose(crops[5], crops[0][:, ::-1, :])


def test_image_transformer():
    from paddle_tpu.utils.image_util import ImageTransformer
    t = ImageTransformer(transpose=(2, 0, 1), channel_swap=(2, 1, 0),
                         mean=np.array([1.0, 2.0, 3.0]))
    hwc = np.ones((4, 4, 3), np.float32)
    out = t.transformer(hwc)
    assert out.shape == (3, 4, 4)
    # channel swap reverses, then per-channel mean subtracts
    np.testing.assert_allclose(out[0], np.zeros((4, 4)))
    np.testing.assert_allclose(out[2], np.ones((4, 4)) - 3.0)


# --- proto shim -----------------------------------------------------------

def test_proto_framework_is_proto_compat():
    from paddle_tpu import proto
    from paddle_tpu.fluid import proto_compat
    assert proto.framework is proto_compat


# --- distributed launchers ------------------------------------------------

_COLLECTIVE_CHILD = textwrap.dedent("""
    import json, os, sys
    print(json.dumps({k: os.environ.get(k) for k in
          ("PADDLE_TRAINER_ID", "PADDLE_CURRENT_ENDPOINT",
           "PADDLE_TRAINERS_NUM", "PADDLE_TRAINER_ENDPOINTS")}))
""")


def test_launch_collective_env_contract(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(_COLLECTIVE_CHILD)
    from paddle_tpu.distributed import launch
    log_dir = tmp_path / "logs"
    launch.launch(["--nproc_per_node=2", "--started_port=7311",
                   f"--log_dir={log_dir}", str(script)])
    ranks = {}
    for i in range(2):
        seen = json.loads((log_dir / f"workerlog.{i}").read_text().strip())
        ranks[seen["PADDLE_TRAINER_ID"]] = seen
    assert set(ranks) == {"0", "1"}
    for rid, env in ranks.items():
        assert env["PADDLE_TRAINERS_NUM"] == "2"
        eps = env["PADDLE_TRAINER_ENDPOINTS"].split(",")
        assert len(eps) == 2 and env["PADDLE_CURRENT_ENDPOINT"] == \
            eps[int(rid)]


def test_launch_parent_holds_no_backend_and_defaults_to_one_process(
        tmp_path):
    """A chip belongs to one process: a launcher parent that initializes
    a JAX backend (to count devices, say) holds the chip its children
    need.  Run the launcher's __main__ and look: no backend came up in
    it.  With no count given it starts ONE process, which drives every
    chip of its host."""
    script = tmp_path / "child.py"
    script.write_text(_COLLECTIVE_CHILD)
    log_dir = tmp_path / "logs"
    parent = textwrap.dedent(f"""
        import runpy, sys
        sys.argv = ["launch", "--started_port=7331",
                    "--log_dir={log_dir}", "{script}"]
        runpy.run_module("paddle_tpu.distributed.launch",
                         run_name="__main__")
        from jax._src import xla_bridge  # no public "is a backend up?"
        assert not xla_bridge._backends, xla_bridge._backends
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", parent],
                         capture_output=True, text=True, timeout=120,
                         cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert [f for f in os.listdir(log_dir)
            if f.startswith("workerlog")] == ["workerlog.0"]
    seen = json.loads((log_dir / "workerlog.0").read_text().strip())
    assert seen["PADDLE_TRAINERS_NUM"] == "1"


def test_launch_rejects_short_selected_gpus(tmp_path):
    """Mis-sized --selected_gpus must fail BEFORE spawning anything (a
    partial group would block forever in collective rendezvous)."""
    script = tmp_path / "child.py"
    script.write_text("raise SystemExit('must never run')")
    from paddle_tpu.distributed import launch
    with pytest.raises(ValueError, match="selected_gpus"):
        launch.launch(["--selected_gpus=0,1", "--nproc_per_node=4",
                       str(script)])


def test_launch_print_config_flag_parses():
    from paddle_tpu.distributed.launch import _parse_args
    args = _parse_args(["--print_config=False", "x.py"])
    assert args.print_config is False
    args = _parse_args(["--print_config=true", "x.py"])
    assert args.print_config is True


def test_launch_failure_propagates_and_terminates(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(textwrap.dedent("""
        import os, sys, time
        if os.environ["PADDLE_TRAINER_ID"] == "0":
            sys.exit(3)
        time.sleep(60)  # must be torn down, not waited for
    """))
    from paddle_tpu.distributed import launch
    import time
    t0 = time.time()
    with pytest.raises(subprocess.CalledProcessError):
        launch.launch(["--nproc_per_node=2", "--started_port=7321",
                       f"--log_dir={tmp_path / 'logs'}", str(script)])
    assert time.time() - t0 < 30  # rank 1's sleep(60) did not block us


_PS_CHILD = textwrap.dedent("""
    import json, os
    role = os.environ["TRAINING_ROLE"]
    rec = {"role": role,
           "pservers": os.environ["PADDLE_PSERVERS"],
           "port": os.environ["PADDLE_PORT"],
           "trainers": os.environ["PADDLE_TRAINERS_NUM"],
           "tid": os.environ.get("PADDLE_TRAINER_ID")}
    print(json.dumps(rec))
    # pservers would serve forever; exit promptly so the test stays fast —
    # the launcher also terminates servers once trainers finish
""")


def test_launch_ps_env_contract(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(_PS_CHILD)
    from paddle_tpu.distributed import launch_ps
    log_dir = tmp_path / "pslogs"
    launch_ps.launch(["--server_num=1", "--worker_num=2",
                      "--start_port=7331", f"--log_dir={log_dir}",
                      str(script)])
    server = json.loads((log_dir / "serverlog.0").read_text().strip())
    assert server["role"] == "PSERVER" and server["port"] == "7331"
    for i in range(2):
        worker = json.loads(
            (log_dir / f"workerlog.{i}").read_text().strip())
        assert worker["role"] == "TRAINER" and worker["tid"] == str(i)
        assert worker["trainers"] == "2"


def test_toplevel_modules_importable():
    for name in ("compat", "distributed", "proto", "utils"):
        assert hasattr(paddle_tpu, name)
