"""Inference stack: Config/Predictor/zero-copy handles + the C API
(reference analysis_predictor.h:82, inference/capi/)."""
import ctypes
import os

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu import inference


@pytest.fixture(scope="module")
def saved_model(tmp_path_factory):
    """Train a small model and export it."""
    path = str(tmp_path_factory.mktemp("model") / "infer")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4, 8], append_batch_size=False)
        y = layers.data("y", [4, 1], append_batch_size=False)
        hidden = layers.fc(x, 16, act="relu")
        pred = layers.fc(hidden, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.executor.Scope()):
        exe.run(startup)
        rng = np.random.RandomState(0)
        xa = rng.rand(4, 8).astype(np.float32)
        ya = xa.sum(1, keepdims=True).astype(np.float32)
        for _ in range(20):
            exe.run(main, feed={"x": xa, "y": ya}, fetch_list=[loss])
        fluid.io.save_inference_model(path, ["x"], [pred], exe, main_program=main)
        (expected,) = exe.run(main, feed={"x": xa, "y": ya}, fetch_list=[pred])
    return path, xa, np.asarray(expected)


def test_predictor_handles_roundtrip(saved_model):
    path, xa, expected = saved_model
    config = inference.Config(path)
    pred = inference.create_predictor(config)
    assert pred.get_input_names() == ["x"]
    assert len(pred.get_output_names()) == 1

    inp = pred.get_input_handle("x")
    inp.copy_from_cpu(xa)
    assert pred.run() is True
    out = pred.get_output_handle(pred.get_output_names()[0])
    np.testing.assert_allclose(out.copy_to_cpu(), expected, rtol=1e-5, atol=1e-6)
    assert out.shape() == [4, 1]

    # positional run (legacy PaddlePredictor::Run)
    (o2,) = pred.run([xa])
    np.testing.assert_allclose(o2, expected, rtol=1e-5, atol=1e-6)


def test_predictor_clone_shares_weights(saved_model):
    path, xa, expected = saved_model
    p1 = inference.create_predictor(inference.Config(path))
    p2 = p1.clone()
    (o2,) = p2.run([xa])
    np.testing.assert_allclose(o2, expected, rtol=1e-5, atol=1e-6)


def test_share_external_data_device_array(saved_model):
    import jax

    path, xa, expected = saved_model
    pred = inference.create_predictor(inference.Config(path))
    dev = jax.device_put(xa)
    pred.get_input_handle("x").share_external_data(dev)
    pred.run()
    out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-6)


def test_tensorrt_raises():
    with pytest.raises(NotImplementedError, match="XLA"):
        inference.Config("/tmp/x").enable_tensorrt_engine()


def test_c_api_end_to_end(saved_model):
    from paddle_tpu import native

    lib = native.load_capi()
    if lib is None:
        pytest.fail(f"C API failed to build: {native.capi_error()}")
    path, xa, expected = saved_model

    err = ctypes.c_char_p()
    h = lib.PD_PredictorCreate(path.encode(), ctypes.byref(err))
    assert h, err.value
    try:
        assert lib.PD_GetInputNum(h) == 1
        assert lib.PD_GetOutputNum(h) == 1
        buf = ctypes.create_string_buffer(256)
        assert lib.PD_GetInputName(h, 0, buf, 256) == 0
        assert buf.value == b"x"
        assert lib.PD_GetOutputName(h, 0, buf, 256) == 0
        out_name = buf.value

        arr = np.ascontiguousarray(xa)
        shape = (ctypes.c_longlong * 2)(4, 8)
        rc = lib.PD_SetInputFloat(
            h, b"x", arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            shape, 2, ctypes.byref(err),
        )
        assert rc == 0, err.value
        assert lib.PD_PredictorRun(h, ctypes.byref(err)) == 0, err.value

        out = (ctypes.c_float * 8)()
        oshape = (ctypes.c_longlong * 4)()
        ndim = ctypes.c_int()
        n = lib.PD_GetOutputFloat(
            h, out_name, out, 8, oshape, 4, ctypes.byref(ndim),
            ctypes.byref(err),
        )
        assert n == 4, err.value
        assert ndim.value == 2 and list(oshape[:2]) == [4, 1]
        np.testing.assert_allclose(
            np.asarray(out[:4]).reshape(4, 1), expected, rtol=1e-5, atol=1e-5
        )
    finally:
        lib.PD_PredictorDestroy(h)


def test_c_api_standalone_binary(saved_model, tmp_path):
    """A NON-Python process consumes the C API: compile capi_example.c,
    dlopen the shim (which self-initializes the embedded interpreter),
    load the model, run inference (the reference's Go/R client story)."""
    import shutil
    import subprocess

    if shutil.which("gcc") is None:
        pytest.skip("no gcc")
    from paddle_tpu import native

    lib = native.load_capi()
    if lib is None:
        pytest.fail(f"C API failed to build: {native.capi_error()}")
    so = native._hashed_so_path(native._CAPI_SRC, "libpaddle_tpu_capi")
    path, xa, expected = saved_model

    src = os.path.join(os.path.dirname(native.__file__), "capi_example.c")
    demo = str(tmp_path / "demo")
    # the shim links libpython itself: the client builds with -ldl only
    r = subprocess.run(["gcc", src, "-o", demo, "-ldl"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr

    env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
    r = subprocess.run([demo, so, path], capture_output=True, text=True,
                       env=env, timeout=180)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "4 elems" in r.stdout  # [4,1] output of the saved model


def test_pd_run_once_scripting_entry(saved_model):
    """PD_RunOnce: the handle-free one-shot entry for .C-style FFI
    clients (clients/r/mobilenet.R)."""
    import ctypes

    import numpy as np

    from paddle_tpu import native

    lib = native.load_capi()
    assert lib is not None, native.capi_error()
    path, xa, expected = saved_model

    err = ctypes.c_char_p()  # argtypes declared centrally in load_capi()
    # discover the exported output name through the predictor API
    h = lib.PD_PredictorCreate(path.encode(), ctypes.byref(err))
    assert h, err.value
    buf = ctypes.create_string_buffer(256)
    assert lib.PD_GetOutputName(ctypes.c_void_p(h), 0, buf, 256) == 0
    out_name = buf.value
    lib.PD_PredictorDestroy(ctypes.c_void_p(h))

    xa = np.ascontiguousarray(xa, dtype=np.float32)
    shape = (ctypes.c_int * xa.ndim)(*xa.shape)  # int32: R-friendly entry
    out = (ctypes.c_float * 64)()
    n = lib.PD_RunOnce(
        path.encode(), b"x",
        xa.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), shape, xa.ndim,
        out_name, out, 64, ctypes.byref(err))
    assert n == expected.size, (n, err.value)
    np.testing.assert_allclose(
        np.asarray(out[: int(n)]).reshape(expected.shape), expected,
        rtol=1e-4)


def test_pd_run_once_r_convention(saved_model):
    """PD_RunOnceR: the .C-shaped wrapper (all pointer args, void return)
    that clients/r/mobilenet.R drives."""
    import ctypes

    import numpy as np

    from paddle_tpu import native

    lib = native.load_capi()
    assert lib is not None, native.capi_error()
    path, xa, expected = saved_model

    err = ctypes.c_char_p()
    h = lib.PD_PredictorCreate(path.encode(), ctypes.byref(err))
    assert h, err.value
    buf = ctypes.create_string_buffer(256)
    assert lib.PD_GetOutputName(ctypes.c_void_p(h), 0, buf, 256) == 0
    out_name = buf.value
    lib.PD_PredictorDestroy(ctypes.c_void_p(h))

    lib.PD_RunOnceR.restype = None
    xa = np.ascontiguousarray(xa, dtype=np.float32)
    model_p = ctypes.c_char_p(path.encode())
    in_p = ctypes.c_char_p(b"x")
    out_p = ctypes.c_char_p(out_name)
    shape = (ctypes.c_int * 2)(*xa.shape)
    ndim = ctypes.c_int(2)
    out = (ctypes.c_float * 64)()
    cap = ctypes.c_double(64)
    n = ctypes.c_double(0)
    lib.PD_RunOnceR(
        ctypes.byref(model_p), ctypes.byref(in_p),
        xa.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        shape, ctypes.byref(ndim), ctypes.byref(out_p), out,
        ctypes.byref(cap), ctypes.byref(n))
    assert int(n.value) == expected.size
    np.testing.assert_allclose(
        np.asarray(out[: int(n.value)]).reshape(expected.shape), expected,
        rtol=1e-4)
