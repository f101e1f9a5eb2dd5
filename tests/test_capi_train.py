"""C training API (capi.cc PD_Trainer* + native/train_demo.c): the
reference's pure-C++ training-driver story (fluid/train/demo)."""
import os
import subprocess

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def train_model(tmp_path):
    """A linear-regression TRAIN program saved via save_train_model."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [16, 2], "float32")
        y = fluid.data("y", [16, 1], "float32")
        pred = layers.fc(x, 1)
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.3).minimize(loss)
    path = str(tmp_path / "train_model")
    scope = fluid.executor.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_train_model(exe, path, ["x", "y"], loss,
                                  main_program=main, startup_program=startup)
    return path


def test_save_load_train_model_roundtrip(train_model):
    scope = fluid.executor.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        main, startup, feeds, loss_name = fluid.io.load_train_model(
            exe, train_model)
        assert feeds == ["x", "y"]
        rng = np.random.RandomState(0)
        xv = rng.randn(16, 2).astype("f4")
        yv = (xv @ np.asarray([[2.0], [-3.0]], "f4") + 0.5).astype("f4")
        losses = []
        for _ in range(40):
            (lv,) = exe.run(main, feed={"x": xv, "y": yv},
                            fetch_list=[loss_name])
            losses.append(float(np.asarray(lv).reshape(())))
    assert losses[-1] < losses[0] * 0.1, (losses[0], losses[-1])


def test_ctrainer_host_class(train_model):
    from paddle_tpu.native.train_host import CTrainer

    tr = CTrainer(train_model)
    assert tr.get_feed_names() == ["x", "y"]
    rng = np.random.RandomState(1)
    xv = rng.randn(16, 2).astype("f4")
    yv = (xv @ np.asarray([[2.0], [-3.0]], "f4") + 0.5).astype("f4")
    tr.set_input("x", xv.ravel(), [16, 2])
    tr.set_input("y", yv.ravel(), [16, 1])
    first = tr.run_step()
    for _ in range(39):
        last = tr.run_step()
    assert last < first * 0.1, (first, last)


def test_c_train_demo_binary(train_model, tmp_path):
    """Compile and run the pure-C driver against the saved train model."""
    import shutil

    if shutil.which("gcc") is None:
        pytest.skip("no gcc")
    from paddle_tpu import native

    lib = native.load_capi()
    if lib is None:
        pytest.fail(f"C API failed to build: {native.capi_error()}")
    so = native._hashed_so_path(native._CAPI_SRC, "libpaddle_tpu_capi")

    src = os.path.join(os.path.dirname(native.__file__), "train_demo.c")
    demo = str(tmp_path / "train_demo")
    r = subprocess.run(["gcc", src, "-o", demo, "-ldl"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr

    env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
    r = subprocess.run([demo, so, train_model], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "TRAIN DEMO OK" in r.stdout
