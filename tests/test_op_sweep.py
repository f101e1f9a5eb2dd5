"""Per-op sweep: every registered op gets a numpy-oracle OpTest case or an
explicit, justified exemption (reference contract: tests/unittests/
op_test.py — ~700 test_*_op.py files; here one parameterized table).

test_coverage asserts CASES ∪ EXEMPT == registry.registered_ops().
"""
import numpy as np
import pytest

from paddle_tpu.ops import registry

from op_test import OpTest

R = np.random.RandomState  # shorthand


def f32(a):
    return np.asarray(a, np.float32)


def _pos(rng, *shape):
    """Positive, away from 0 (safe for log/sqrt/div grads)."""
    return f32(rng.uniform(0.3, 1.5, shape))


def _mix(rng, *shape):
    """Mixed sign, away from kinks at 0 (safe for abs/relu grads)."""
    return f32(rng.uniform(0.25, 1.25, shape) * np.where(rng.rand(*shape) < 0.5, -1, 1))


def _softmax(z, axis=-1):
    e = np.exp(z - z.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


# ---------------------------------------------------------------------------
# case table: op_type -> list of zero-arg factories returning OpTest
# ---------------------------------------------------------------------------

CASES = {}


def case(op_type):
    def deco(fn):
        CASES.setdefault(op_type, []).append(fn)
        return fn

    return deco


def unary(op_type, np_fn, inp=_mix, grad=True, attrs=None, tol=1e-5, grad_tol=1e-2):
    def make():
        x = inp(R(7), 3, 5)
        return OpTest(
            op_type, {"X": x},
            lambda ins, a, fn=np_fn: {"Out": [f32(fn(ins["X"][0], a))]},
            attrs=attrs, grad=("X",) if grad else (), tol=tol, grad_tol=grad_tol,
        )

    CASES.setdefault(op_type, []).append(make)


# ---- activations / unary elementwise --------------------------------------
unary("abs", lambda x, a: np.abs(x))
unary("acos", lambda x, a: np.arccos(x), inp=lambda r, *s: f32(r.uniform(-0.8, 0.8, s)))
unary("asin", lambda x, a: np.arcsin(x), inp=lambda r, *s: f32(r.uniform(-0.8, 0.8, s)))
unary("atan", lambda x, a: np.arctan(x))
unary("ceil", lambda x, a: np.ceil(x), grad=False)
unary("floor", lambda x, a: np.floor(x), grad=False)
unary("round", lambda x, a: np.round(x), grad=False)
unary("sign", lambda x, a: np.sign(x), grad=False)
unary("cos", lambda x, a: np.cos(x))
unary("sin", lambda x, a: np.sin(x))
unary("tan", lambda x, a: np.tan(x))
unary("sinh", lambda x, a: np.sinh(x))
unary("cosh", lambda x, a: np.cosh(x))
unary("erf", lambda x, a: np.vectorize(__import__("math").erf)(x).astype(np.float32))
unary("exp", lambda x, a: np.exp(x))
unary("log", lambda x, a: np.log(x), inp=_pos)
unary("log2", lambda x, a: np.log2(x), inp=_pos)
unary("log10", lambda x, a: np.log10(x), inp=_pos)
unary("log1p", lambda x, a: np.log1p(x), inp=_pos)
unary("sqrt", lambda x, a: np.sqrt(x), inp=_pos)
unary("rsqrt", lambda x, a: 1.0 / np.sqrt(x), inp=_pos)
unary("square", lambda x, a: np.square(x))
unary("reciprocal", lambda x, a: 1.0 / x, inp=_pos)
unary("sigmoid", lambda x, a: 1 / (1 + np.exp(-x)))
unary("logsigmoid", lambda x, a: -np.log1p(np.exp(-x)))
unary("tanh", lambda x, a: np.tanh(x))
unary("relu", lambda x, a: np.maximum(x, 0))
unary("relu6", lambda x, a: np.clip(x, 0, 6.0))
unary("softplus", lambda x, a: np.log1p(np.exp(x)))
unary("softsign", lambda x, a: x / (1 + np.abs(x)))
unary("silu", lambda x, a: x / (1 + np.exp(-x)))
unary("swish", lambda x, a: x / (1 + np.exp(-x)))
unary("mish", lambda x, a: x * np.tanh(np.log1p(np.exp(x))))
unary("leaky_relu", lambda x, a: np.where(x > 0, x, 0.02 * x))
unary("elu", lambda x, a: np.where(x > 0, x, np.exp(x) - 1.0))
unary(
    "gelu",
    lambda x, a: x * 0.5 * (1 + np.vectorize(__import__("math").erf)(x / np.sqrt(2.0))),
    tol=1e-4,
)
unary("hard_sigmoid", lambda x, a: np.clip(0.2 * x + 0.5, 0, 1))
unary("hard_swish", lambda x, a: x * np.clip(x + 3.0, 0, 6.0) / 6.0)
unary("thresholded_relu", lambda x, a: np.where(x > 1.0, x, 0.0), inp=lambda r, *s: f32(r.uniform(0.5, 1.6, s)))
unary("hard_shrink", lambda x, a: np.where(np.abs(x) > 0.5, x, 0.0), inp=lambda r, *s: f32(r.uniform(0.7, 1.5, s)))
unary("soft_shrink", lambda x, a: np.sign(x) * np.maximum(np.abs(x) - 0.5, 0), inp=lambda r, *s: f32(r.uniform(0.8, 1.5, s) * np.where(r.rand(*s) < 0.5, -1, 1)))
unary("scale", lambda x, a: x * 3.0 + 0.5, attrs={"scale": 3.0, "bias": 0.5})
unary("increment", lambda x, a: x + 2.0, attrs={"step": 2.0})
unary("assign", lambda x, a: x)
unary("pow", lambda x, a: np.power(x, 2.0), inp=_pos, attrs={"factor": 2.0})
unary("clip", lambda x, a: np.clip(x, -0.5, 0.5), attrs={"min": -0.5, "max": 0.5}, grad=False)
unary("logsumexp", lambda x, a: f32([np.log(np.sum(np.exp(x)))]), attrs={"axis": [], "keepdim": False})
unary("softmax", lambda x, a: _softmax(x))
unary("log_softmax", lambda x, a: np.log(_softmax(x)))
unary("mean", lambda x, a: f32([x.mean()]))
unary("squared_l2_norm", lambda x, a: f32([np.sum(x * x)]))


@case("cast")
def _cast():
    x = _mix(R(3), 3, 4)
    return OpTest(
        "cast", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0].astype(np.int32)]},
        attrs={"in_dtype": np.dtype("float32"), "out_dtype": np.dtype("int32")},
    )


# ---- binary elementwise ----------------------------------------------------


def binary(op_type, np_fn, y_inp=None, grad=("X", "Y"), attrs=None):
    def make():
        rng = R(11)
        x = _mix(rng, 3, 4)
        if y_inp is None:
            # keep |x-y| >= 0.15: min/max kinks stay out of finite-diff reach
            y = x + f32(np.where(rng.rand(3, 4) < 0.5, -1, 1) * rng.uniform(0.15, 0.8, (3, 4)))
        else:
            y = y_inp(rng, 3, 4)
        return OpTest(
            op_type, {"X": x, "Y": y},
            lambda ins, a, fn=np_fn: {"Out": [fn(ins["X"][0], ins["Y"][0])]},
            attrs=attrs, grad=grad,
        )

    CASES.setdefault(op_type, []).append(make)


binary("elementwise_add", lambda x, y: x + y)
binary("elementwise_sub", lambda x, y: x - y)
binary("elementwise_mul", lambda x, y: x * y)
binary("elementwise_div", lambda x, y: x / y, y_inp=_pos)
binary("elementwise_min", lambda x, y: np.minimum(x, y))
binary("elementwise_max", lambda x, y: np.maximum(x, y))
binary("maximum", lambda x, y: np.maximum(x, y))
binary("minimum", lambda x, y: np.minimum(x, y))


@case("elementwise_pow")
def _epow():
    rng = R(2)
    x, y = _pos(rng, 3, 4), _pos(rng, 3, 4)
    return OpTest(
        "elementwise_pow", {"X": x, "Y": y},
        lambda ins, a: {"Out": [np.power(ins["X"][0], ins["Y"][0])]},
        grad=("X", "Y"),
    )


@case("elementwise_mod")
def _emod():
    rng = R(5)
    x = rng.randint(1, 50, (3, 4)).astype(np.int32)
    y = rng.randint(1, 7, (3, 4)).astype(np.int32)
    return OpTest(
        "elementwise_mod", {"X": x, "Y": y},
        lambda ins, a: {"Out": [np.mod(ins["X"][0], ins["Y"][0])]},
    )


@case("elementwise_floordiv")
def _efdiv():
    rng = R(5)
    x = rng.randint(1, 50, (3, 4)).astype(np.int32)
    y = rng.randint(1, 7, (3, 4)).astype(np.int32)
    return OpTest(
        "elementwise_floordiv", {"X": x, "Y": y},
        lambda ins, a: {"Out": [ins["X"][0] // ins["Y"][0]]},
    )


@case("elementwise_add")
def _eadd_axis():
    """paddle axis-broadcast: y [4] into x [2,4,3] at axis=1."""
    rng = R(13)
    x = _mix(rng, 2, 4, 3)
    y = _mix(rng, 4)
    return OpTest(
        "elementwise_add", {"X": x, "Y": y},
        lambda ins, a: {"Out": [ins["X"][0] + ins["Y"][0].reshape(1, 4, 1)]},
        attrs={"axis": 1}, grad=("X", "Y"),
    )


@case("sum")
def _sum():
    rng = R(17)
    xs = [_mix(rng, 3, 4) for _ in range(3)]
    return OpTest(
        "sum", {"X": xs},
        lambda ins, a: {"Out": [ins["X"][0] + ins["X"][1] + ins["X"][2]]},
        grad=("X",),
    )


# ---- compare / logical -----------------------------------------------------


def cmp_case(op_type, np_fn):
    def make():
        rng = R(23)
        x = rng.randint(0, 3, (3, 4)).astype(np.float32)
        y = rng.randint(0, 3, (3, 4)).astype(np.float32)
        return OpTest(
            op_type, {"X": x, "Y": y},
            lambda ins, a, fn=np_fn: {"Out": [fn(ins["X"][0], ins["Y"][0])]},
        )

    CASES.setdefault(op_type, []).append(make)


cmp_case("equal", np.equal)
cmp_case("not_equal", np.not_equal)
cmp_case("less_than", np.less)
cmp_case("less_equal", np.less_equal)
cmp_case("greater_than", np.greater)
cmp_case("greater_equal", np.greater_equal)


def logical_case(op_type, np_fn, nin=2):
    def make():
        rng = R(29)
        x = rng.rand(3, 4) > 0.5
        y = rng.rand(3, 4) > 0.5
        ins = {"X": x} if nin == 1 else {"X": x, "Y": y}
        return OpTest(
            op_type, ins,
            lambda i, a, fn=np_fn: {
                "Out": [fn(i["X"][0]) if nin == 1 else fn(i["X"][0], i["Y"][0])]
            },
        )

    CASES.setdefault(op_type, []).append(make)


logical_case("logical_and", np.logical_and)
logical_case("logical_or", np.logical_or)
logical_case("logical_xor", np.logical_xor)
logical_case("logical_not", np.logical_not, nin=1)


@case("allclose")
def _allclose():
    x = f32([[1.0, 2.0], [3.0, 4.0]])
    return OpTest(
        "allclose", {"Input": x, "Other": x + 1e-7},
        lambda ins, a: {"Out": [np.asarray(True)]},
        attrs={"rtol": 1e-5, "atol": 1e-8},
    )


def isx_case(op_type, np_fn, reduced):
    def make():
        x = f32([[1.0, np.inf], [np.nan, 2.0]])
        if reduced:
            oracle = lambda ins, a, fn=np_fn: {"Out": [np.asarray([fn(ins["X"][0]).any() if op_type != "isfinite" else fn(ins["X"][0]).all()])]}
        else:
            oracle = lambda ins, a, fn=np_fn: {"Out": [fn(ins["X"][0])]}
        return OpTest(op_type, {"X": x}, oracle)

    CASES.setdefault(op_type, []).append(make)


isx_case("isfinite", np.isfinite, True)
isx_case("isinf", np.isinf, True)
isx_case("isnan", np.isnan, True)
isx_case("isfinite_v2", np.isfinite, False)
isx_case("isinf_v2", np.isinf, False)
isx_case("isnan_v2", np.isnan, False)


# ---- reductions ------------------------------------------------------------


def reduce_case(op_type, np_fn, grad=True, boolean=False):
    def make():
        rng = R(31)
        x = (rng.rand(2, 3, 4) > 0.5) if boolean else _mix(rng, 2, 3, 4)
        return OpTest(
            op_type, {"X": x},
            lambda ins, a, fn=np_fn: {"Out": [fn(ins["X"][0], axis=1)]},
            attrs={"dim": [1], "keep_dim": False},
            grad=("X",) if grad else (),
        )

    def make_all():
        rng = R(37)
        x = (rng.rand(2, 3) > 0.5) if boolean else _pos(rng, 2, 3)
        return OpTest(
            op_type, {"X": x},
            lambda ins, a, fn=np_fn: {"Out": [np.asarray([fn(ins["X"][0])])]},
            attrs={"reduce_all": True, "keep_dim": False, "dim": [0]},
            grad=("X",) if grad else (),
        )

    CASES.setdefault(op_type, []).extend([make, make_all])


reduce_case("reduce_sum", np.sum)
reduce_case("reduce_mean", np.mean)
reduce_case("reduce_max", np.max)
reduce_case("reduce_min", np.min)
reduce_case("reduce_prod", np.prod)
reduce_case("reduce_all", np.all, grad=False, boolean=True)
reduce_case("reduce_any", np.any, grad=False, boolean=True)


@case("frobenius_norm")
def _frob():
    x = _mix(R(41), 3, 4)
    return OpTest(
        "frobenius_norm", {"X": x},
        lambda ins, a: {"Out": [f32([np.sqrt(np.sum(np.square(ins["X"][0])))])]},
        attrs={"reduce_all": True, "keep_dim": False}, grad=("X",),
    )


@case("p_norm")
def _pnorm():
    x = _mix(R(43), 3, 4)
    return OpTest(
        "p_norm", {"X": x},
        lambda ins, a: {"Out": [np.linalg.norm(ins["X"][0], ord=2, axis=-1).astype(np.float32)]},
        attrs={"porder": 2.0, "axis": -1, "keepdim": False}, grad=("X",),
    )


@case("norm")
def _norm():
    x = _mix(R(47), 3, 4)

    def oracle(ins, a):
        n = np.sqrt(np.sum(np.square(ins["X"][0]), axis=-1, keepdims=True) + 1e-10)
        return {"Out": [f32(ins["X"][0] / n)], "Norm": [f32(n)]}

    return OpTest(
        "norm", {"X": x}, oracle, attrs={"axis": -1},
        outputs={"Out": 1, "Norm": 1}, grad=("X",),
    )


@case("trace")
def _trace():
    x = _mix(R(53), 4, 4)
    return OpTest(
        "trace", {"Input": x},
        lambda ins, a: {"Out": [np.trace(ins["Input"][0]).astype(np.float32)]},
        grad=("Input",),
    )


# ---- matmul family ---------------------------------------------------------


@case("matmul")
def _matmul():
    rng = R(59)
    return OpTest(
        "matmul", {"X": _mix(rng, 3, 5), "Y": _mix(rng, 2, 5)},
        lambda ins, a: {"Out": [2.0 * ins["X"][0] @ ins["Y"][0].T]},
        attrs={"transpose_Y": True, "alpha": 2.0}, grad=("X", "Y"), grad_tol=2e-2,
    )


@case("matmul_v2")
def _matmul_v2():
    rng = R(61)
    return OpTest(
        "matmul_v2", {"X": _mix(rng, 2, 3, 5), "Y": _mix(rng, 2, 5, 4)},
        lambda ins, a: {"Out": [ins["X"][0] @ ins["Y"][0]]},
        grad=("X", "Y"), grad_tol=2e-2,
    )


@case("mul")
def _mul():
    rng = R(67)
    x, y = _mix(rng, 2, 3, 4), _mix(rng, 12, 5)
    return OpTest(
        "mul", {"X": x, "Y": y},
        lambda ins, a: {"Out": [(ins["X"][0].reshape(2, 12) @ ins["Y"][0]).reshape(2, 5)]},
        attrs={"x_num_col_dims": 1, "y_num_col_dims": 1}, grad=("X", "Y"), grad_tol=2e-2,
    )


@case("dot")
def _dot():
    rng = R(71)
    x, y = _mix(rng, 3, 4), _mix(rng, 3, 4)
    return OpTest(
        "dot", {"X": x, "Y": y},
        lambda ins, a: {"Out": [np.sum(ins["X"][0] * ins["Y"][0], -1, keepdims=True)]},
        grad=("X", "Y"),
    )


@case("addmm")
def _addmm():
    rng = R(73)
    return OpTest(
        "addmm", {"Input": _mix(rng, 2, 4), "X": _mix(rng, 2, 3), "Y": _mix(rng, 3, 4)},
        lambda ins, a: {"Out": [0.5 * ins["Input"][0] + 2.0 * (ins["X"][0] @ ins["Y"][0])]},
        attrs={"Alpha": 2.0, "Beta": 0.5}, grad=("Input", "X", "Y"), grad_tol=2e-2,
    )


@case("kron")
def _kron():
    rng = R(79)
    return OpTest(
        "kron", {"X": _mix(rng, 2, 3), "Y": _mix(rng, 2, 2)},
        lambda ins, a: {"Out": [np.kron(ins["X"][0], ins["Y"][0])]},
        grad=("X", "Y"),
    )


@case("matrix_power")
def _matpow():
    x = f32(np.eye(3) * 0.8 + R(83).rand(3, 3) * 0.1)
    return OpTest(
        "matrix_power", {"X": x},
        lambda ins, a: {"Out": [np.linalg.matrix_power(ins["X"][0], 3).astype(np.float32)]},
        attrs={"n": 3}, grad=("X",), grad_tol=3e-2,
    )


@case("inverse")
def _inverse():
    x = f32(np.eye(3) + R(89).rand(3, 3) * 0.2)
    return OpTest(
        "inverse", {"Input": x},
        lambda ins, a: {"Output": [np.linalg.inv(ins["Input"][0]).astype(np.float32)]},
        outputs={"Output": 1}, grad=("Input",), grad_tol=3e-2, tol=1e-4,
    )


@case("cholesky")
def _cholesky():
    rng = R(97)
    a = f32(rng.rand(3, 3) * 0.3)
    x = a @ a.T + np.eye(3, dtype=np.float32)
    return OpTest(
        "cholesky", {"X": x},
        lambda ins, a_: {"Out": [np.linalg.cholesky(ins["X"][0]).astype(np.float32)]},
        tol=1e-4,
    )


@case("clip_by_norm")
def _clip_by_norm():
    x = _mix(R(101), 3, 4) * 5.0

    def oracle(ins, a):
        n = np.sqrt(np.sum(np.square(ins["X"][0])))
        return {"Out": [f32(ins["X"][0] * (1.0 / max(n / 1.0, 1.0)))]}

    return OpTest("clip_by_norm", {"X": x}, oracle, attrs={"max_norm": 1.0})


@case("prelu")
def _prelu():
    rng = R(103)
    x = _mix(rng, 2, 3)
    alpha = f32([0.25])
    return OpTest(
        "prelu", {"X": x, "Alpha": alpha},
        lambda ins, a: {"Out": [np.where(ins["X"][0] > 0, ins["X"][0], 0.25 * ins["X"][0])]},
        attrs={"mode": "all"}, grad=("X",),
    )


@case("maxout")
def _maxout():
    x = _mix(R(107), 2, 6, 3)
    return OpTest(
        "maxout", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0].reshape(2, 2, 3, 3).max(axis=2)]},
        attrs={"groups": 3}, grad=("X",),
    )


# ---- manipulation ----------------------------------------------------------


@case("reshape")
def _reshape():
    x = _mix(R(109), 2, 6)
    return OpTest(
        "reshape", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0].reshape(3, 4)]},
        attrs={"shape": [3, -1]}, grad=("X",),
    )


@case("reshape2")
def _reshape2():
    x = _mix(R(113), 2, 6)
    return OpTest(
        "reshape2", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0].reshape(3, 4)]},
        attrs={"shape": [3, 4]}, outputs={"Out": 1, "XShape": 1}, grad=("X",),
    )


@case("transpose")
def _transpose():
    x = _mix(R(127), 2, 3, 4)
    return OpTest(
        "transpose", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0].transpose(2, 0, 1)]},
        attrs={"axis": [2, 0, 1]}, grad=("X",),
    )


@case("transpose2")
def _transpose2():
    x = _mix(R(131), 2, 3)
    return OpTest(
        "transpose2", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0].T]},
        attrs={"axis": [1, 0]}, outputs={"Out": 1, "XShape": 1}, grad=("X",),
    )


@case("concat")
def _concat():
    rng = R(137)
    xs = [_mix(rng, 2, 3), _mix(rng, 2, 2)]
    return OpTest(
        "concat", {"X": xs},
        lambda ins, a: {"Out": [np.concatenate(ins["X"], axis=1)]},
        attrs={"axis": 1}, grad=("X",),
    )


@case("split")
def _split():
    x = _mix(R(139), 2, 6)
    return OpTest(
        "split", {"X": x},
        lambda ins, a: {"Out": list(np.split(ins["X"][0], 3, axis=1))},
        attrs={"num": 3, "axis": 1}, outputs={"Out": 3}, grad=("X",),
    )


@case("slice")
def _slice():
    x = _mix(R(149), 4, 5)
    return OpTest(
        "slice", {"Input": x},
        lambda ins, a: {"Out": [ins["Input"][0][1:3, 0:4]]},
        attrs={"axes": [0, 1], "starts": [1, 0], "ends": [3, 4], "decrease_axis": []},
        grad=("Input",),
    )


@case("strided_slice")
def _strided_slice():
    x = _mix(R(151), 6, 5)
    return OpTest(
        "strided_slice", {"Input": x},
        lambda ins, a: {"Out": [ins["Input"][0][0:6:2]]},
        attrs={"axes": [0], "starts": [0], "ends": [6], "strides": [2]},
        grad=("Input",),
    )


@case("stack")
def _stack():
    rng = R(157)
    xs = [_mix(rng, 2, 3) for _ in range(3)]
    return OpTest(
        "stack", {"X": xs},
        lambda ins, a: {"Y": [np.stack(ins["X"], axis=1)]},
        attrs={"axis": 1}, outputs={"Y": 1}, grad=("X",),
    )


@case("unstack")
def _unstack():
    x = _mix(R(163), 3, 2, 4)
    return OpTest(
        "unstack", {"X": x},
        lambda ins, a: {"Y": [ins["X"][0][i] for i in range(3)]},
        attrs={"axis": 0, "num": 3}, outputs={"Y": 3}, grad=("X",),
    )


@case("unbind")
def _unbind():
    x = _mix(R(167), 2, 3, 2)
    return OpTest(
        "unbind", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0][:, i] for i in range(3)]},
        attrs={"axis": 1}, outputs={"Out": 3}, grad=("X",),
    )


@case("squeeze")
def _squeeze():
    x = _mix(R(173), 2, 1, 3)
    return OpTest(
        "squeeze", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0].squeeze(1)]},
        attrs={"axes": [1]}, grad=("X",),
    )


@case("squeeze2")
def _squeeze2():
    x = _mix(R(179), 2, 1, 3)
    return OpTest(
        "squeeze2", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0].squeeze(1)]},
        attrs={"axes": [1]}, outputs={"Out": 1, "XShape": 1}, grad=("X",),
    )


@case("unsqueeze")
def _unsqueeze():
    x = _mix(R(181), 2, 3)
    return OpTest(
        "unsqueeze", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0][:, None, :]]},
        attrs={"axes": [1]}, grad=("X",),
    )


@case("unsqueeze2")
def _unsqueeze2():
    x = _mix(R(191), 2, 3)
    return OpTest(
        "unsqueeze2", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0][:, None, :]]},
        attrs={"axes": [1]}, outputs={"Out": 1, "XShape": 1}, grad=("X",),
    )


@case("flatten")
def _flatten():
    x = _mix(R(193), 2, 3, 4)
    return OpTest(
        "flatten", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0].reshape(2, 12)]},
        attrs={"axis": 1}, grad=("X",),
    )


@case("flatten2")
def _flatten2():
    x = _mix(R(197), 2, 3, 4)
    return OpTest(
        "flatten2", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0].reshape(2, 12)]},
        attrs={"axis": 1}, outputs={"Out": 1, "XShape": 1}, grad=("X",),
    )


@case("flatten_contiguous_range")
def _flatten_cr():
    x = _mix(R(199), 2, 3, 4, 2)
    return OpTest(
        "flatten_contiguous_range", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0].reshape(2, 12, 2)]},
        attrs={"start_axis": 1, "stop_axis": 2},
        outputs={"Out": 1, "XShape": 1}, grad=("X",),
    )


@case("expand")
def _expand():
    x = _mix(R(211), 2, 3)
    return OpTest(
        "expand", {"X": x},
        lambda ins, a: {"Out": [np.tile(ins["X"][0], (2, 1))]},
        attrs={"expand_times": [2, 1]}, grad=("X",),
    )


@case("expand_v2")
def _expand_v2():
    x = _mix(R(223), 1, 3)
    return OpTest(
        "expand_v2", {"X": x},
        lambda ins, a: {"Out": [np.broadcast_to(ins["X"][0], (4, 3))]},
        attrs={"shape": [4, 3]}, grad=("X",),
    )


@case("expand_as")
def _expand_as():
    rng = R(227)
    x, tgt = _mix(rng, 1, 3), _mix(rng, 4, 3)
    return OpTest(
        "expand_as", {"X": x, "target_tensor": tgt},
        lambda ins, a: {"Out": [np.broadcast_to(ins["X"][0], (4, 3))]},
        grad=("X",),
    )


@case("tile")
def _tile():
    x = _mix(R(229), 2, 3)
    return OpTest(
        "tile", {"X": x},
        lambda ins, a: {"Out": [np.tile(ins["X"][0], (2, 2))]},
        attrs={"repeat_times": [2, 2]}, grad=("X",),
    )


@case("gather")
def _gather():
    rng = R(233)
    x = _mix(rng, 5, 3)
    idx = np.asarray([0, 2, 4], np.int32)
    return OpTest(
        "gather", {"X": x, "Index": idx},
        lambda ins, a: {"Out": [ins["X"][0][ins["Index"][0]]]},
        grad=("X",),
    )


@case("gather_nd")
def _gather_nd():
    rng = R(239)
    x = _mix(rng, 3, 4)
    idx = np.asarray([[0, 1], [2, 3]], np.int32)
    return OpTest(
        "gather_nd", {"X": x, "Index": idx},
        lambda ins, a: {"Out": [f32([ins["X"][0][0, 1], ins["X"][0][2, 3]])]},
        grad=("X",),
    )


@case("scatter")
def _scatter():
    rng = R(241)
    x = _mix(rng, 5, 3)
    ids = np.asarray([1, 3], np.int32)
    upd = _mix(rng, 2, 3)

    def oracle(ins, a):
        out = ins["X"][0].copy()
        out[ins["Ids"][0]] = ins["Updates"][0]
        return {"Out": [out]}

    return OpTest(
        "scatter", {"X": x, "Ids": ids, "Updates": upd}, oracle,
        attrs={"overwrite": True}, grad=("X", "Updates"),
    )


@case("scatter_nd_add")
def _scatter_nd_add():
    rng = R(251)
    x = _mix(rng, 4, 3)
    idx = np.asarray([[1], [3]], np.int32)
    upd = _mix(rng, 2, 3)

    def oracle(ins, a):
        out = ins["X"][0].copy()
        out[1] += ins["Updates"][0][0]
        out[3] += ins["Updates"][0][1]
        return {"Out": [out]}

    return OpTest(
        "scatter_nd_add", {"X": x, "Index": idx, "Updates": upd}, oracle,
        grad=("X", "Updates"),
    )


@case("pad")
def _pad():
    x = _mix(R(257), 2, 3)
    return OpTest(
        "pad", {"X": x},
        lambda ins, a: {"Out": [np.pad(ins["X"][0], [(1, 0), (0, 2)], constant_values=0.5)]},
        attrs={"paddings": [1, 0, 0, 2], "pad_value": 0.5}, grad=("X",),
    )


@case("pad2d")
def _pad2d():
    x = _mix(R(263), 2, 3, 4, 4)
    return OpTest(
        "pad2d", {"X": x},
        lambda ins, a: {
            "Out": [np.pad(ins["X"][0], [(0, 0), (0, 0), (1, 2), (0, 1)])]
        },
        attrs={"paddings": [1, 2, 0, 1], "mode": "constant", "pad_value": 0.0},
        grad=("X",),
    )


@case("pad3d")
def _pad3d():
    x = _mix(R(269), 1, 2, 3, 3, 3)
    return OpTest(
        "pad3d", {"X": x},
        lambda ins, a: {
            "Out": [np.pad(ins["X"][0], [(0, 0), (0, 0), (1, 1), (1, 0), (0, 1)])]
        },
        attrs={"paddings": [0, 1, 1, 0, 1, 1], "mode": "constant", "value": 0.0},
        grad=("X",),
    )


@case("flip")
def _flip():
    x = _mix(R(271), 2, 3)
    return OpTest(
        "flip", {"X": x},
        lambda ins, a: {"Out": [np.flip(ins["X"][0], axis=(1,))]},
        attrs={"axis": [1]}, grad=("X",),
    )


@case("roll")
def _roll():
    x = _mix(R(277), 2, 4)
    return OpTest(
        "roll", {"X": x},
        lambda ins, a: {"Out": [np.roll(ins["X"][0], 1, axis=1)]},
        attrs={"shifts": [1], "axis": [1]}, grad=("X",),
    )


@case("where")
def _where():
    rng = R(281)
    cond = rng.rand(3, 4) > 0.5
    x, y = _mix(rng, 3, 4), _mix(rng, 3, 4)
    return OpTest(
        "where", {"Condition": cond, "X": x, "Y": y},
        lambda ins, a: {"Out": [np.where(ins["Condition"][0], ins["X"][0], ins["Y"][0])]},
        grad=("X", "Y"),
    )


@case("arg_max")
def _arg_max():
    x = _mix(R(283), 3, 5)
    return OpTest(
        "arg_max", {"X": x},
        lambda ins, a: {"Out": [np.argmax(ins["X"][0], -1)]},
        attrs={"axis": -1},
    )


@case("arg_min")
def _arg_min():
    x = _mix(R(293), 3, 5)
    return OpTest(
        "arg_min", {"X": x},
        lambda ins, a: {"Out": [np.argmin(ins["X"][0], -1)]},
        attrs={"axis": -1},
    )


@case("argsort")
def _argsort():
    x = _mix(R(307), 3, 5)

    def oracle(ins, a):
        idx = np.argsort(ins["X"][0], -1)
        return {"Out": [np.take_along_axis(ins["X"][0], idx, -1)], "Indices": [idx]}

    return OpTest(
        "argsort", {"X": x}, oracle, attrs={"axis": -1},
        outputs={"Out": 1, "Indices": 1}, grad=("X",),
    )


@case("top_k")
def _top_k():
    x = f32(R(311).permutation(np.arange(18) * 0.3 - 2.0).reshape(3, 6))

    def oracle(ins, a):
        idx = np.argsort(-ins["X"][0], -1)[:, :2]
        return {"Out": [np.take_along_axis(ins["X"][0], idx, -1)], "Indices": [idx]}

    return OpTest(
        "top_k", {"X": x}, oracle, attrs={"k": 2},
        outputs={"Out": 1, "Indices": 1}, grad=("X",),
    )


@case("top_k_v2")
def _top_k_v2():
    x = f32(R(313).permutation(np.arange(18) * 0.3 - 2.0).reshape(3, 6))

    def oracle(ins, a):
        idx = np.argsort(-ins["X"][0], -1)[:, :2]
        return {"Out": [np.take_along_axis(ins["X"][0], idx, -1)], "Indices": [idx]}

    return OpTest(
        "top_k_v2", {"X": x}, oracle, attrs={"k": 2, "axis": -1, "largest": True},
        outputs={"Out": 1, "Indices": 1}, grad=("X",),
    )


@case("cumsum")
def _cumsum():
    x = _mix(R(317), 3, 4)
    return OpTest(
        "cumsum", {"X": x},
        lambda ins, a: {"Out": [np.cumsum(ins["X"][0], axis=1)]},
        attrs={"axis": 1}, grad=("X",),
    )


@case("tril_triu")
def _tril_triu():
    x = _mix(R(331), 4, 4)
    return OpTest(
        "tril_triu", {"X": x},
        lambda ins, a: {"Out": [np.tril(ins["X"][0])]},
        attrs={"lower": True, "diagonal": 0}, grad=("X",),
    )


@case("diag_v2")
def _diag_v2():
    x = _mix(R(337), 4)
    return OpTest(
        "diag_v2", {"X": x},
        lambda ins, a: {"Out": [np.diag(ins["X"][0])]},
        attrs={"offset": 0, "padding_value": 0.0},
    )


@case("index_select")
def _index_select():
    rng = R(347)
    x = _mix(rng, 4, 3)
    idx = np.asarray([0, 2], np.int32)
    return OpTest(
        "index_select", {"X": x, "Index": idx},
        lambda ins, a: {"Out": [ins["X"][0][[0, 2]]]},
        attrs={"dim": 0}, grad=("X",),
    )


@case("take_along_axis")
def _take_along_axis():
    rng = R(349)
    x = _mix(rng, 3, 4)
    idx = rng.randint(0, 4, (3, 2)).astype(np.int32)
    return OpTest(
        "take_along_axis", {"Input": x, "Index": idx},
        lambda ins, a: {"Result": [np.take_along_axis(ins["Input"][0], ins["Index"][0], 1)]},
        attrs={"Axis": 1}, outputs={"Result": 1}, grad=("Input",),
    )


@case("meshgrid")
def _meshgrid():
    rng = R(353)
    xs = [_mix(rng, 3), _mix(rng, 4)]

    def oracle(ins, a):
        a_, b_ = np.meshgrid(ins["X"][0], ins["X"][1], indexing="ij")
        return {"Out": [a_, b_]}

    return OpTest("meshgrid", {"X": xs}, oracle, outputs={"Out": 2}, grad=("X",))


@case("shard_index")
def _shard_index():
    ids = np.asarray([[1], [7], [12], [19]], np.int32)

    def oracle(ins, a):
        x = ins["X"][0]
        shard = x // 10 == 1
        return {"Out": [np.where(shard, x % 10, -1).astype(x.dtype)]}

    return OpTest(
        "shard_index", {"X": ids}, oracle,
        attrs={"index_num": 20, "nshards": 2, "shard_id": 1, "ignore_value": -1},
    )


@case("one_hot")
def _one_hot():
    x = np.asarray([[0], [2], [1]], np.int32)

    def oracle(ins, a):
        return {"Out": [np.eye(3, dtype=np.float32)[ins["X"][0].reshape(-1)]]}

    return OpTest("one_hot", {"X": x}, oracle, attrs={"depth": 3})


@case("one_hot_v2")
def _one_hot_v2():
    x = np.asarray([0, 2, 1], np.int32)

    def oracle(ins, a):
        return {"Out": [np.eye(3, dtype=np.float32)[ins["X"][0]]]}

    return OpTest("one_hot_v2", {"X": x}, oracle, attrs={"depth": 3})


# ---- creation --------------------------------------------------------------


@case("fill_constant")
def _fill_constant():
    return OpTest(
        "fill_constant", {},
        lambda ins, a: {"Out": [np.full((2, 3), 1.5, np.float32)]},
        attrs={"shape": [2, 3], "value": 1.5, "dtype": np.dtype("float32")},
    )


@case("fill_constant_batch_size_like")
def _fill_cbsl():
    x = _mix(R(359), 4, 2)
    return OpTest(
        "fill_constant_batch_size_like", {"Input": x},
        lambda ins, a: {"Out": [np.full((4, 7), 2.0, np.float32)]},
        attrs={"shape": [1, 7], "value": 2.0, "dtype": np.dtype("float32"),
               "input_dim_idx": 0, "output_dim_idx": 0},
    )


@case("fill_zeros_like")
def _fill_zeros_like():
    x = _mix(R(367), 2, 3)
    return OpTest(
        "fill_zeros_like", {"X": x},
        lambda ins, a: {"Out": [np.zeros_like(ins["X"][0])]},
    )


@case("fill_any_like")
def _fill_any_like():
    x = _mix(R(373), 2, 3)
    return OpTest(
        "fill_any_like", {"X": x},
        lambda ins, a: {"Out": [np.full_like(ins["X"][0], 3.5)]},
        attrs={"value": 3.5},
    )


@case("eye")
def _eye():
    return OpTest(
        "eye", {},
        lambda ins, a: {"Out": [np.eye(3, 4, dtype=np.float32)]},
        attrs={"num_rows": 3, "num_columns": 4, "dtype": np.dtype("float32")},
    )


@case("assign_value")
def _assign_value():
    vals = [1.0, 2.0, 3.0, 4.0]
    return OpTest(
        "assign_value", {},
        lambda ins, a: {"Out": [f32(vals).reshape(2, 2)]},
        attrs={"shape": [2, 2], "dtype": np.dtype("float32"), "fp32_values": vals},
    )


@case("range")
def _range():
    return OpTest(
        "range", {},
        lambda ins, a: {"Out": [np.arange(1, 9, 2, np.int32)]},
        attrs={"start": 1, "end": 9, "step": 2, "dtype": np.dtype("int32")},
    )


@case("linspace")
def _linspace():
    return OpTest(
        "linspace", {},
        lambda ins, a: {"Out": [np.linspace(0.0, 1.0, 5).astype(np.float32)]},
        attrs={"start": 0.0, "stop": 1.0, "num": 5, "dtype": np.dtype("float32")},
    )


@case("shape")
def _shape():
    x = _mix(R(379), 2, 5)
    return OpTest(
        "shape", {"Input": x},
        lambda ins, a: {"Out": [np.asarray([2, 5], np.int32)]},
    )


# ---- nn: conv / pool / norm ------------------------------------------------


def _np_conv2d(x, w, stride=1, pad=0):
    n, cin, h, wd = x.shape
    cout, _, kh, kw = w.shape
    xp = np.pad(x, [(0, 0), (0, 0), (pad, pad), (pad, pad)])
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (wd + 2 * pad - kw) // stride + 1
    out = np.zeros((n, cout, oh, ow), np.float32)
    for i in range(oh):
        for j in range(ow):
            patch = xp[:, :, i * stride:i * stride + kh, j * stride:j * stride + kw]
            out[:, :, i, j] = np.einsum("nchw,ochw->no", patch, w)
    return out


@case("conv2d")
def _conv2d():
    rng = R(383)
    x = _mix(rng, 2, 3, 5, 5)
    w = _mix(rng, 4, 3, 3, 3) * 0.2
    return OpTest(
        "conv2d", {"Input": x, "Filter": w},
        lambda ins, a: {"Output": [_np_conv2d(ins["Input"][0], ins["Filter"][0], 1, 1)]},
        attrs={"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1], "groups": 1},
        outputs={"Output": 1}, grad=("Input", "Filter"), tol=1e-4, grad_tol=2e-2,
    )


@case("conv3d")
def _conv3d():
    rng = R(389)
    x = _mix(rng, 1, 2, 3, 4, 4)
    w = _mix(rng, 3, 2, 2, 2, 2) * 0.2

    def oracle(ins, a):
        import jax.numpy as jnp
        import jax.lax as lax

        out = lax.conv_general_dilated(
            jnp.asarray(ins["Input"][0]), jnp.asarray(ins["Filter"][0]),
            (1, 1, 1), [(0, 0)] * 3,
            dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        )
        return {"Output": [np.asarray(out)]}

    # oracle via jax.lax on *numpy* inputs is independent of the Program
    # path under test (the executor+emitter), matching the reference's use
    # of scipy in conv oracles
    return OpTest(
        "conv3d", {"Input": x, "Filter": w}, oracle,
        attrs={"strides": [1, 1, 1], "paddings": [0, 0, 0], "dilations": [1, 1, 1], "groups": 1},
        outputs={"Output": 1}, grad=("Input", "Filter"), tol=1e-4, grad_tol=2e-2,
    )


@case("depthwise_conv2d")
def _depthwise_conv2d():
    rng = R(397)
    x = _mix(rng, 1, 3, 5, 5)
    w = _mix(rng, 3, 1, 3, 3) * 0.3

    def oracle(ins, a):
        xx, ww = ins["Input"][0], ins["Filter"][0]
        out = np.zeros((1, 3, 3, 3), np.float32)
        for c in range(3):
            out[:, c:c + 1] = _np_conv2d(xx[:, c:c + 1], ww[c:c + 1])
        return {"Output": [out]}

    return OpTest(
        "depthwise_conv2d", {"Input": x, "Filter": w}, oracle,
        attrs={"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1]},
        outputs={"Output": 1}, grad=("Input", "Filter"), tol=1e-4, grad_tol=2e-2,
    )


@case("conv2d_transpose")
def _conv2d_transpose():
    rng = R(401)
    x = _mix(rng, 1, 2, 3, 3)
    w = _mix(rng, 2, 3, 2, 2) * 0.3

    def oracle(ins, a):
        xx, ww = ins["Input"][0], ins["Filter"][0]
        out = np.zeros((1, 3, 4, 4), np.float32)
        for i in range(3):
            for j in range(3):
                out[:, :, i:i + 2, j:j + 2] += np.einsum(
                    "nc,cohw->nohw", xx[:, :, i, j], ww
                )
        return {"Output": [out]}

    return OpTest(
        "conv2d_transpose", {"Input": x, "Filter": w}, oracle,
        attrs={"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1], "groups": 1},
        outputs={"Output": 1}, grad=("Input", "Filter"), tol=1e-4, grad_tol=2e-2,
    )


@case("pool2d")
def _pool2d_max():
    x = _mix(R(409), 1, 2, 4, 4)

    def oracle(ins, a):
        xx = ins["X"][0]
        out = np.zeros((1, 2, 2, 2), np.float32)
        for i in range(2):
            for j in range(2):
                out[:, :, i, j] = xx[:, :, 2 * i:2 * i + 2, 2 * j:2 * j + 2].max((2, 3))
        return {"Out": [out]}

    return OpTest(
        "pool2d", {"X": x}, oracle,
        attrs={"pooling_type": "max", "ksize": [2, 2], "strides": [2, 2], "paddings": [0, 0]},
        grad=("X",),
    )


@case("pool2d")
def _pool2d_avg_global():
    x = _mix(R(419), 1, 2, 4, 4)
    return OpTest(
        "pool2d", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0].mean((2, 3), keepdims=True)]},
        attrs={"pooling_type": "avg", "global_pooling": True, "ksize": [1, 1]},
        grad=("X",),
    )


@case("batch_norm")
def _batch_norm():
    rng = R(421)
    x = _mix(rng, 3, 2, 4)
    scale, bias = _pos(rng, 2), _mix(rng, 2)
    mean, var = np.zeros(2, np.float32), np.ones(2, np.float32)

    def oracle(ins, a):
        xx = ins["X"][0]
        m = xx.mean((0, 2))
        v = xx.var((0, 2))
        y = (xx - m[None, :, None]) / np.sqrt(v[None, :, None] + 1e-5)
        y = y * ins["Scale"][0][None, :, None] + ins["Bias"][0][None, :, None]
        return {"Y": [f32(y)], "SavedMean": [f32(m)]}

    return OpTest(
        "batch_norm",
        {"X": x, "Scale": scale, "Bias": bias, "Mean": mean, "Variance": var},
        oracle, attrs={"epsilon": 1e-5, "momentum": 0.9, "data_layout": "NCHW"},
        outputs={"Y": 1, "MeanOut": 1, "VarianceOut": 1, "SavedMean": 1, "SavedVariance": 1},
        tol=1e-4,
    )


@case("fused_conv_bn")
def _fused_conv_bn():
    # 1x1 NHWC so the numpy oracle is one einsum; the kernel-shape sweep
    # (strides, SAME/VALID, kxk, odd channels) lives in
    # tests/test_conv_bn_fusion.py
    rng = R(77)
    x = _mix(rng, 2, 4, 4, 3)
    w = _mix(rng, 5, 3, 1, 1)
    scale, bias = _pos(rng, 5), _mix(rng, 5)
    mean, var = np.zeros(5, np.float32), np.ones(5, np.float32)

    def oracle(ins, a):
        xx, ww = ins["Input"][0], ins["Filter"][0]
        z = np.einsum("nhwc,oc->nhwo", xx, ww[:, :, 0, 0])
        m = z.mean((0, 1, 2))
        v = z.var((0, 1, 2))
        y = (z - m) / np.sqrt(v + 1e-5) * ins["Scale"][0] + ins["Bias"][0]
        return {"Y": [f32(np.maximum(y, 0.0))], "SavedMean": [f32(m)]}

    return OpTest(
        "fused_conv_bn",
        {"Input": x, "Filter": w, "Scale": scale, "Bias": bias,
         "Mean": mean, "Variance": var},
        oracle,
        attrs={"epsilon": 1e-5, "momentum": 0.9, "data_format": "NHWC",
               "data_layout": "NHWC", "with_relu": True},
        outputs={"Y": 1, "MeanOut": 1, "VarianceOut": 1, "SavedMean": 1,
                 "SavedVariance": 1},
        tol=1e-4,
    )


@case("layer_norm")
def _layer_norm():
    rng = R(431)
    x = _mix(rng, 3, 4)
    scale, bias = _pos(rng, 4), _mix(rng, 4)

    def oracle(ins, a):
        xx = ins["X"][0]
        m = xx.mean(-1, keepdims=True)
        v = xx.var(-1, keepdims=True)
        y = (xx - m) / np.sqrt(v + 1e-5) * ins["Scale"][0] + ins["Bias"][0]
        return {"Y": [f32(y)]}

    return OpTest(
        "layer_norm", {"X": x, "Scale": scale, "Bias": bias}, oracle,
        attrs={"epsilon": 1e-5, "begin_norm_axis": 1},
        outputs={"Y": 1, "Mean": 1, "Variance": 1},
        grad=("X", "Scale", "Bias"), tol=1e-4, grad_tol=2e-2,
    )


@case("group_norm")
def _group_norm():
    rng = R(433)
    x = _mix(rng, 2, 4, 3)
    scale, bias = _pos(rng, 4), _mix(rng, 4)

    def oracle(ins, a):
        xx = ins["X"][0].reshape(2, 2, 2, 3)
        m = xx.mean((2, 3), keepdims=True)
        v = xx.var((2, 3), keepdims=True)
        y = ((xx - m) / np.sqrt(v + 1e-5)).reshape(2, 4, 3)
        y = y * ins["Scale"][0][None, :, None] + ins["Bias"][0][None, :, None]
        return {"Y": [f32(y)]}

    return OpTest(
        "group_norm", {"X": x, "Scale": scale, "Bias": bias}, oracle,
        attrs={"groups": 2, "epsilon": 1e-5},
        outputs={"Y": 1, "Mean": 1, "Variance": 1},
        grad=("X",), tol=1e-4, grad_tol=2e-2,
    )


@case("instance_norm")
def _instance_norm():
    rng = R(439)
    x = _mix(rng, 2, 3, 4)

    def oracle(ins, a):
        xx = ins["X"][0]
        m = xx.mean(-1, keepdims=True)
        v = xx.var(-1, keepdims=True)
        return {"Y": [f32((xx - m) / np.sqrt(v + 1e-5))]}

    return OpTest(
        "instance_norm", {"X": x}, oracle, attrs={"epsilon": 1e-5},
        outputs={"Y": 1, "SavedMean": 1, "SavedVariance": 1},
        grad=("X",), tol=1e-4, grad_tol=2e-2,
    )


@case("dropout")
def _dropout_test_mode():
    x = _mix(R(443), 3, 4)
    return OpTest(
        "dropout", {"X": x},
        lambda ins, a: {"Out": [ins["X"][0] * 0.7]},
        attrs={"dropout_prob": 0.3, "is_test": True,
               "dropout_implementation": "downgrade_in_infer"},
        outputs={"Out": 1, "Mask": 1}, grad=("X",),
    )


@case("lookup_table")
def _lookup_table():
    rng = R(449)
    w = _mix(rng, 6, 3)
    ids = np.asarray([[0], [5], [2]], np.int32)
    return OpTest(
        "lookup_table", {"W": w, "Ids": ids},
        lambda ins, a: {"Out": [ins["W"][0][[0, 5, 2]]]},
        grad=("W",),
    )


@case("lookup_table_v2")
def _lookup_table_v2():
    rng = R(457)
    w = _mix(rng, 6, 3)
    ids = np.asarray([[0, 5], [2, 1]], np.int32)
    return OpTest(
        "lookup_table_v2", {"W": w, "Ids": ids},
        lambda ins, a: {"Out": [ins["W"][0][ins["Ids"][0]]]},
        grad=("W",),
    )


@case("embedding_with_scaled_gradient")
def _emb_scaled():
    rng = R(461)
    w = _mix(rng, 6, 3)
    ids = np.asarray([1, 4], np.int32)
    return OpTest(
        "embedding_with_scaled_gradient", {"W": w, "Ids": ids},
        lambda ins, a: {"Out": [ins["W"][0][ins["Ids"][0]]]},
        grad=("W",),
    )


# ---- losses ----------------------------------------------------------------


@case("softmax_with_cross_entropy")
def _swce():
    rng = R(463)
    logits = _mix(rng, 4, 5)
    label = rng.randint(0, 5, (4, 1)).astype(np.int32)

    def oracle(ins, a):
        sm = _softmax(ins["Logits"][0])
        lbl = ins["Label"][0].reshape(-1)
        loss = -np.log(sm[np.arange(4), lbl])[:, None]
        return {"Softmax": [f32(sm)], "Loss": [f32(loss)]}

    return OpTest(
        "softmax_with_cross_entropy", {"Logits": logits, "Label": label},
        oracle, outputs={"Softmax": 1, "Loss": 1}, grad=("Logits",),
    )


@case("cross_entropy")
def _cross_entropy():
    rng = R(467)
    x = _softmax(_mix(rng, 4, 5)).astype(np.float32)
    label = rng.randint(0, 5, (4, 1)).astype(np.int32)

    def oracle(ins, a):
        lbl = ins["Label"][0].reshape(-1)
        return {"Y": [f32(-np.log(ins["X"][0][np.arange(4), lbl]))[:, None]]}

    return OpTest(
        "cross_entropy", {"X": x, "Label": label}, oracle,
        outputs={"Y": 1}, grad=("X",),
    )


@case("cross_entropy2")
def _cross_entropy2():
    rng = R(479)
    x = _softmax(_mix(rng, 4, 5)).astype(np.float32)
    label = rng.randint(0, 5, (4, 1)).astype(np.int32)

    def oracle(ins, a):
        lbl = ins["Label"][0].reshape(-1)
        y = f32(-np.log(ins["X"][0][np.arange(4), lbl]))[:, None]
        return {"Y": [y], "MatchX": [np.exp(-y)]}

    return OpTest(
        "cross_entropy2", {"X": x, "Label": label}, oracle,
        outputs={"Y": 1, "XShape": 1, "MatchX": 1}, grad=("X",),
    )


@case("sigmoid_cross_entropy_with_logits")
def _scel():
    rng = R(487)
    x = _mix(rng, 3, 4)
    label = rng.randint(0, 2, (3, 4)).astype(np.float32)

    def oracle(ins, a):
        xx, ll = ins["X"][0], ins["Label"][0]
        loss = np.maximum(xx, 0) - xx * ll + np.log1p(np.exp(-np.abs(xx)))
        return {"Out": [f32(loss)]}

    return OpTest(
        "sigmoid_cross_entropy_with_logits", {"X": x, "Label": label},
        oracle, grad=("X",),
    )


@case("bce_loss")
def _bce():
    rng = R(491)
    x = f32(rng.uniform(0.1, 0.9, (3, 4)))
    label = rng.randint(0, 2, (3, 4)).astype(np.float32)

    def oracle(ins, a):
        xx, ll = ins["X"][0], ins["Label"][0]
        return {"Out": [f32(-(ll * np.log(xx) + (1 - ll) * np.log(1 - xx)))]}

    return OpTest("bce_loss", {"X": x, "Label": label}, oracle, grad=("X",))


@case("square_error_cost")
def _sec():
    rng = R(499)
    x, y = _mix(rng, 3, 4), _mix(rng, 3, 4)
    return OpTest(
        "square_error_cost", {"X": x, "Y": y},
        lambda ins, a: {"Out": [np.square(ins["X"][0] - ins["Y"][0])]},
        grad=("X", "Y"),
    )


@case("smooth_l1_loss")
def _sl1():
    rng = R(503)
    x, y = _mix(rng, 3, 4), _mix(rng, 3, 4)

    def oracle(ins, a):
        d = ins["X"][0] - ins["Y"][0]
        ad = np.abs(d)
        loss = np.where(ad < 1.0, 0.5 * d * d, ad - 0.5)
        return {"Out": [f32(loss.sum(1, keepdims=True))], "Diff": [f32(d)]}

    return OpTest(
        "smooth_l1_loss", {"X": x, "Y": y}, oracle,
        attrs={"sigma": 1.0}, outputs={"Out": 1, "Diff": 1}, grad=("X", "Y"),
    )


@case("huber_loss")
def _huber():
    rng = R(509)
    x, y = _mix(rng, 3, 4), _mix(rng, 3, 4)

    def oracle(ins, a):
        r = ins["Y"][0] - ins["X"][0]
        ar = np.abs(r)
        loss = np.where(ar <= 1.0, 0.5 * r * r, ar - 0.5)
        return {"Out": [f32(loss)], "Residual": [f32(r)]}

    return OpTest(
        "huber_loss", {"X": x, "Y": y}, oracle, attrs={"delta": 1.0},
        outputs={"Out": 1, "Residual": 1}, grad=("X",),
    )


@case("log_loss")
def _log_loss():
    rng = R(521)
    p = f32(rng.uniform(0.2, 0.8, (4, 1)))
    l = rng.randint(0, 2, (4, 1)).astype(np.float32)

    def oracle(ins, a):
        pp, ll = ins["Predicted"][0], ins["Labels"][0]
        eps = 1e-4
        return {"Loss": [f32(-ll * np.log(pp + eps) - (1 - ll) * np.log(1 - pp + eps))]}

    return OpTest(
        "log_loss", {"Predicted": p, "Labels": l}, oracle,
        attrs={"epsilon": 1e-4}, outputs={"Loss": 1}, grad=("Predicted",),
    )


@case("kldiv_loss")
def _kldiv():
    rng = R(523)
    x = _mix(rng, 3, 4)
    t = _softmax(_mix(rng, 3, 4)).astype(np.float32)

    def oracle(ins, a):
        tt = ins["Target"][0]
        loss = np.where(tt > 0, tt * (np.log(tt) - ins["X"][0]), 0.0)
        return {"Loss": [f32([loss.mean()])]}

    return OpTest(
        "kldiv_loss", {"X": x, "Target": t}, oracle,
        attrs={"reduction": "mean"}, outputs={"Loss": 1}, grad=("X",),
    )


@case("label_smooth")
def _label_smooth():
    x = np.eye(4, dtype=np.float32)[[0, 2, 1]]
    return OpTest(
        "label_smooth", {"X": x},
        lambda ins, a: {"Out": [f32(0.9 * ins["X"][0] + 0.1 / 4)]},
        attrs={"epsilon": 0.1}, grad=("X",),
    )


@case("mse_loss")
def _mse():
    rng = R(541)
    x, y = _mix(rng, 3, 4), _mix(rng, 3, 4)
    return OpTest(
        "mse_loss", {"X": x, "Y": y},
        lambda ins, a: {"Out": [f32([np.mean(np.square(ins["X"][0] - ins["Y"][0]))])]},
        grad=("X", "Y"),
    )


@case("margin_rank_loss")
def _mrl():
    rng = R(547)
    x1, x2 = _mix(rng, 4, 1), _mix(rng, 4, 1)
    label = np.where(rng.rand(4, 1) < 0.5, -1.0, 1.0).astype(np.float32)

    def oracle(ins, a):
        act = np.maximum(0.0, -ins["Label"][0] * (ins["X1"][0] - ins["X2"][0]) + 0.1)
        return {"Out": [f32(act)]}

    return OpTest(
        "margin_rank_loss", {"X1": x1, "X2": x2, "Label": label}, oracle,
        attrs={"margin": 0.1}, outputs={"Out": 1, "Activated": 1},
    )


@case("auc")
def _auc():
    rng = R(701)
    n, nt = 50, 64
    score = f32(rng.rand(n))
    pred = np.stack([1 - score, score], 1)
    label = (score + rng.randn(n) * 0.3 > 0.5).astype(np.int64)[:, None]
    stat = np.zeros((1, nt + 1), np.int64)

    def oracle(ins, a):
        sc = ins["Predict"][0][:, 1]
        lb = ins["Label"][0].reshape(-1)
        sp = np.zeros(nt + 1, np.int64)
        sn = np.zeros(nt + 1, np.int64)
        idx = np.clip((sc * nt).astype(np.int64), 0, nt)
        for i, l in zip(idx, lb):
            (sp if l > 0 else sn)[i] += 1
        pos = np.cumsum(sp[::-1]); neg = np.cumsum(sn[::-1])
        x = np.concatenate([[0], neg]); y = np.concatenate([[0], pos])
        area = np.sum((x[1:] - x[:-1]) * (y[1:] + y[:-1])) / 2.0
        auc_v = f32([area / max(pos[-1] * neg[-1], 1)])
        return {"AUC": [auc_v],
                "StatPosOut": [sp.reshape(1, -1)],
                "StatNegOut": [sn.reshape(1, -1)]}

    return OpTest(
        "auc", {"Predict": pred, "Label": label, "StatPos": stat, "StatNeg": stat},
        oracle, attrs={"num_thresholds": nt},
        outputs={"AUC": 1, "StatPosOut": 1, "StatNegOut": 1}, tol=1e-4,
    )


@case("accuracy")
def _accuracy():
    idx = np.asarray([[0, 1], [2, 3], [1, 0]], np.int64)
    label = np.asarray([[1], [0], [2]], np.int64)

    def oracle(ins, a):
        return {
            "Accuracy": [f32([1.0 / 3.0])],
            "Correct": [np.asarray([1], np.int32)],
            "Total": [np.asarray([3], np.int32)],
        }

    return OpTest(
        "accuracy", {"Indices": idx, "Label": label}, oracle,
        outputs={"Accuracy": 1, "Correct": 1, "Total": 1},
    )


# ---- optimizer update ops --------------------------------------------------


@case("sgd")
def _sgd():
    rng = R(557)
    p, g = _mix(rng, 3, 4), _mix(rng, 3, 4)
    lr = f32([0.1])
    return OpTest(
        "sgd", {"Param": p, "Grad": g, "LearningRate": lr},
        lambda ins, a: {"ParamOut": [ins["Param"][0] - 0.1 * ins["Grad"][0]]},
        outputs={"ParamOut": 1},
    )


@case("momentum")
def _momentum():
    rng = R(563)
    p, g, v = _mix(rng, 3), _mix(rng, 3), _mix(rng, 3)
    lr = f32([0.1])

    def oracle(ins, a):
        vo = 0.9 * ins["Velocity"][0] + ins["Grad"][0]
        return {"ParamOut": [f32(ins["Param"][0] - 0.1 * vo)], "VelocityOut": [f32(vo)]}

    return OpTest(
        "momentum", {"Param": p, "Grad": g, "Velocity": v, "LearningRate": lr},
        oracle, attrs={"mu": 0.9},
        outputs={"ParamOut": 1, "VelocityOut": 1},
    )


@case("adam")
def _adam():
    rng = R(569)
    p, g = _mix(rng, 4), _mix(rng, 4)
    m1, m2 = _mix(rng, 4) * 0.1, _pos(rng, 4) * 0.01
    b1p, b2p = f32([0.9]), f32([0.999])
    lr = f32([0.01])

    def oracle(ins, a):
        b1, b2, eps = 0.9, 0.999, 1e-8
        gg = ins["Grad"][0]
        m1o = b1 * ins["Moment1"][0] + (1 - b1) * gg
        m2o = b2 * ins["Moment2"][0] + (1 - b2) * gg * gg
        lr_t = 0.01 * np.sqrt(1 - ins["Beta2Pow"][0][0]) / (1 - ins["Beta1Pow"][0][0])
        po = ins["Param"][0] - lr_t * m1o / (np.sqrt(m2o) + eps)
        return {
            "ParamOut": [f32(po)], "Moment1Out": [f32(m1o)], "Moment2Out": [f32(m2o)],
            "Beta1PowOut": [f32([0.9 * 0.9])], "Beta2PowOut": [f32([0.999 * 0.999])],
        }

    return OpTest(
        "adam",
        {"Param": p, "Grad": g, "Moment1": m1, "Moment2": m2,
         "Beta1Pow": b1p, "Beta2Pow": b2p, "LearningRate": lr},
        oracle, attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
        outputs={"ParamOut": 1, "Moment1Out": 1, "Moment2Out": 1,
                 "Beta1PowOut": 1, "Beta2PowOut": 1},
        tol=1e-4,
    )


@case("adamw")
def _adamw():
    rng = R(571)
    p, g = _mix(rng, 4), _mix(rng, 4)
    m1, m2 = np.zeros(4, np.float32), np.zeros(4, np.float32)
    b1p, b2p = f32([0.9]), f32([0.999])
    lr = f32([0.01])

    def oracle(ins, a):
        b1, b2, eps = 0.9, 0.999, 1e-8
        gg = ins["Grad"][0]
        m1o = (1 - b1) * gg
        m2o = (1 - b2) * gg * gg
        lr_t = 0.01 * np.sqrt(1 - ins["Beta2Pow"][0][0]) / (1 - ins["Beta1Pow"][0][0])
        po = ins["Param"][0] - lr_t * m1o / (np.sqrt(m2o) + eps)
        po = po - 0.01 * 0.01 * ins["Param"][0]
        return {"ParamOut": [f32(po)]}

    return OpTest(
        "adamw",
        {"Param": p, "Grad": g, "Moment1": m1, "Moment2": m2,
         "Beta1Pow": b1p, "Beta2Pow": b2p, "LearningRate": lr},
        oracle, attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8, "coeff": 0.01},
        outputs={"ParamOut": 1, "Moment1Out": 1, "Moment2Out": 1,
                 "Beta1PowOut": 1, "Beta2PowOut": 1},
        tol=1e-4,
    )


@case("adamax")
def _adamax():
    rng = R(577)
    p, g = _mix(rng, 4), _mix(rng, 4)
    m, inf = np.zeros(4, np.float32), np.zeros(4, np.float32)
    b1p = f32([0.9])
    lr = f32([0.01])

    def oracle(ins, a):
        b1, b2, eps = 0.9, 0.999, 1e-8
        gg = ins["Grad"][0]
        mo = (1 - b1) * gg
        info = np.maximum(0.0, np.abs(gg))
        po = ins["Param"][0] - (0.01 / (1 - 0.9)) * mo / (info + eps)
        return {"ParamOut": [f32(po)], "MomentOut": [f32(mo)], "InfNormOut": [f32(info)]}

    return OpTest(
        "adamax",
        {"Param": p, "Grad": g, "Moment": m, "InfNorm": inf,
         "Beta1Pow": b1p, "LearningRate": lr},
        oracle, attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
        outputs={"ParamOut": 1, "MomentOut": 1, "InfNormOut": 1}, tol=1e-4,
    )


@case("adagrad")
def _adagrad():
    rng = R(587)
    p, g, m = _mix(rng, 4), _mix(rng, 4), _pos(rng, 4) * 0.1
    lr = f32([0.1])

    def oracle(ins, a):
        mo = ins["Moment"][0] + ins["Grad"][0] ** 2
        po = ins["Param"][0] - 0.1 * ins["Grad"][0] / (np.sqrt(mo) + 1e-6)
        return {"ParamOut": [f32(po)], "MomentOut": [f32(mo)]}

    return OpTest(
        "adagrad", {"Param": p, "Grad": g, "Moment": m, "LearningRate": lr},
        oracle, attrs={"epsilon": 1e-6},
        outputs={"ParamOut": 1, "MomentOut": 1}, tol=1e-4,
    )


@case("decayed_adagrad")
def _decayed_adagrad():
    rng = R(593)
    p, g, m = _mix(rng, 4), _mix(rng, 4), _pos(rng, 4) * 0.1
    lr = f32([0.1])

    def oracle(ins, a):
        mo = 0.95 * ins["Moment"][0] + 0.05 * ins["Grad"][0] ** 2
        po = ins["Param"][0] - 0.1 * ins["Grad"][0] / (np.sqrt(mo) + 1e-6)
        return {"ParamOut": [f32(po)], "MomentOut": [f32(mo)]}

    return OpTest(
        "decayed_adagrad", {"Param": p, "Grad": g, "Moment": m, "LearningRate": lr},
        oracle, attrs={"decay": 0.95, "epsilon": 1e-6},
        outputs={"ParamOut": 1, "MomentOut": 1}, tol=1e-4,
    )


@case("rmsprop")
def _rmsprop():
    rng = R(599)
    p, g = _mix(rng, 4), _mix(rng, 4)
    ms, mom = _pos(rng, 4) * 0.1, np.zeros(4, np.float32)
    lr = f32([0.01])

    def oracle(ins, a):
        ms_out = 0.95 * ins["MeanSquare"][0] + 0.05 * ins["Grad"][0] ** 2
        mo = 0.9 * ins["Moment"][0] + 0.01 * ins["Grad"][0] / np.sqrt(ms_out + 1e-6)
        return {
            "ParamOut": [f32(ins["Param"][0] - mo)],
            "MomentOut": [f32(mo)], "MeanSquareOut": [f32(ms_out)],
        }

    return OpTest(
        "rmsprop",
        {"Param": p, "Grad": g, "MeanSquare": ms, "Moment": mom, "LearningRate": lr},
        oracle, attrs={"decay": 0.95, "epsilon": 1e-6, "momentum": 0.9},
        outputs={"ParamOut": 1, "MomentOut": 1, "MeanSquareOut": 1}, tol=1e-4,
    )


@case("lamb")
def _lamb():
    rng = R(601)
    p, g = _pos(rng, 4), _mix(rng, 4)
    m1, m2 = np.zeros(4, np.float32), np.zeros(4, np.float32)
    b1p, b2p = f32([0.9]), f32([0.999])
    lr = f32([0.01])

    def oracle(ins, a):
        b1, b2, eps, wd = 0.9, 0.999, 1e-6, 0.01
        gg = ins["Grad"][0]
        m1o = (1 - b1) * gg
        m2o = (1 - b2) * gg * gg
        mhat = m1o / (1 - 0.9)
        vhat = m2o / (1 - 0.999)
        r = mhat / (np.sqrt(vhat) + eps) + wd * ins["Param"][0]
        trust = np.linalg.norm(ins["Param"][0]) / np.linalg.norm(r)
        po = ins["Param"][0] - 0.01 * trust * r
        return {"ParamOut": [f32(po)], "Moment1Out": [f32(m1o)], "Moment2Out": [f32(m2o)]}

    return OpTest(
        "lamb",
        {"Param": p, "Grad": g, "Moment1": m1, "Moment2": m2,
         "Beta1Pow": b1p, "Beta2Pow": b2p, "LearningRate": lr},
        oracle, attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-6, "weight_decay": 0.01},
        outputs={"ParamOut": 1, "Moment1Out": 1, "Moment2Out": 1,
                 "Beta1PowOut": 1, "Beta2PowOut": 1},
        tol=1e-4,
    )


@case("lars_momentum")
def _lars():
    rng = R(607)
    p, g, v = _pos(rng, 4), _mix(rng, 4), np.zeros(4, np.float32)
    lr = f32([0.1])

    def oracle(ins, a):
        mu, coeff, wd = 0.9, 0.001, 0.0005
        pn = np.linalg.norm(ins["Param"][0])
        gn = np.linalg.norm(ins["Grad"][0])
        local_lr = 0.1 * coeff * pn / (gn + wd * pn)
        vo = mu * ins["Velocity"][0] + local_lr * (ins["Grad"][0] + wd * ins["Param"][0])
        return {"ParamOut": [f32(ins["Param"][0] - vo)], "VelocityOut": [f32(vo)]}

    return OpTest(
        "lars_momentum",
        {"Param": p, "Grad": g, "Velocity": v, "LearningRate": lr},
        oracle, attrs={"mu": 0.9, "lars_coeff": 0.001, "lars_weight_decay": 0.0005},
        outputs={"ParamOut": 1, "VelocityOut": 1}, tol=1e-4,
    )


@case("ftrl")
def _ftrl():
    rng = R(613)
    p, g = _mix(rng, 4), _mix(rng, 4)
    sq, lin = _pos(rng, 4) * 0.1, np.zeros(4, np.float32)
    lr = f32([0.1])

    def oracle(ins, a):
        gg, pp = ins["Grad"][0], ins["Param"][0]
        sq0 = ins["SquaredAccumulator"][0]
        new_sq = sq0 + gg * gg
        sigma = (np.sqrt(new_sq) - np.sqrt(sq0)) / 0.1
        lin_out = ins["LinearAccumulator"][0] + gg - sigma * pp
        denom = np.sqrt(new_sq) / 0.1
        po = (np.clip(lin_out, 0, 0) - lin_out) / denom
        return {
            "ParamOut": [f32(po)], "SquaredAccumOut": [f32(new_sq)],
            "LinearAccumOut": [f32(lin_out)],
        }

    return OpTest(
        "ftrl",
        {"Param": p, "Grad": g, "SquaredAccumulator": sq,
         "LinearAccumulator": lin, "LearningRate": lr},
        oracle, attrs={"l1": 0.0, "l2": 0.0, "lr_power": -0.5},
        outputs={"ParamOut": 1, "SquaredAccumOut": 1, "LinearAccumOut": 1},
        tol=1e-4,
    )


# ---- sequence / RNN ops ----------------------------------------------------


def _lens(*vals):
    return np.asarray(vals, np.int32)


@case("sequence_mask")
def _sequence_mask():
    return OpTest(
        "sequence_mask", {"X": _lens(2, 4, 0)},
        lambda ins, a: {"Y": [(np.arange(5)[None, :] < ins["X"][0][:, None]).astype(np.int64)]},
        attrs={"maxlen": 5, "out_dtype": np.dtype("int64")}, outputs={"Y": 1},
    )


def _seq_x(rng=None):
    rng = rng or R(619)
    return _mix(rng, 3, 4, 2), _lens(2, 4, 1)


@case("sequence_pool")
def _sequence_pool_avg():
    x, ln = _seq_x()

    def oracle(ins, a):
        xx, ll = ins["X"][0], ins["Length"][0]
        out = np.stack([xx[i, :ll[i]].mean(0) if ll[i] else xx[i, :1].sum(0) * 0
                        for i in range(3)])
        return {"Out": [f32(out)]}

    return OpTest(
        "sequence_pool", {"X": x, "Length": ln}, oracle,
        attrs={"pooltype": "AVERAGE"}, grad=("X",),
    )


@case("sequence_pool")
def _sequence_pool_max():
    x, ln = _seq_x(R(621))

    def oracle(ins, a):
        xx, ll = ins["X"][0], ins["Length"][0]
        out = np.stack([xx[i, :max(ll[i], 1)].max(0) for i in range(3)])
        return {"Out": [f32(out)]}

    return OpTest(
        "sequence_pool", {"X": x, "Length": ln}, oracle,
        attrs={"pooltype": "MAX"}, outputs={"Out": 1, "MaxIndex": 1}, grad=("X",),
    )


@case("sequence_pool")
def _sequence_pool_last():
    x, ln = _seq_x(R(623))

    def oracle(ins, a):
        xx, ll = ins["X"][0], ins["Length"][0]
        out = np.stack([xx[i, max(ll[i] - 1, 0)] for i in range(3)])
        return {"Out": [f32(out)]}

    return OpTest(
        "sequence_pool", {"X": x, "Length": ln}, oracle,
        attrs={"pooltype": "LAST"}, grad=("X",),
    )


@case("sequence_softmax")
def _sequence_softmax():
    rng = R(627)
    x = _mix(rng, 2, 4)
    ln = _lens(3, 4)

    def oracle(ins, a):
        xx, ll = ins["X"][0], ins["Length"][0]
        out = np.zeros_like(xx)
        for i in range(2):
            out[i, :ll[i]] = _softmax(xx[i, :ll[i]])
        return {"Out": [f32(out)]}

    return OpTest(
        "sequence_softmax", {"X": x, "Length": ln}, oracle, grad=("X",),
    )


@case("sequence_reverse")
def _sequence_reverse():
    x, ln = _seq_x(R(631))

    def oracle(ins, a):
        xx, ll = ins["X"][0].copy(), ins["Length"][0]
        out = xx.copy()
        for i in range(3):
            out[i, :ll[i]] = xx[i, :ll[i]][::-1]
        return {"Y": [out]}

    return OpTest(
        "sequence_reverse", {"X": x, "Length": ln}, oracle,
        outputs={"Y": 1}, grad=("X",),
    )


@case("sequence_expand")
def _sequence_expand():
    rng = R(641)
    x, y = _mix(rng, 3, 2), _mix(rng, 3, 4, 5)
    return OpTest(
        "sequence_expand", {"X": x, "Y": y},
        lambda ins, a: {"Out": [np.broadcast_to(ins["X"][0][:, None, :], (3, 4, 2)).copy()]},
        grad=("X",),
    )


@case("sequence_expand_as")
def _sequence_expand_as():
    rng = R(643)
    x, y = _mix(rng, 3, 2), _mix(rng, 3, 5, 1)
    return OpTest(
        "sequence_expand_as", {"X": x, "Y": y},
        lambda ins, a: {"Out": [np.broadcast_to(ins["X"][0][:, None, :], (3, 5, 2)).copy()]},
        grad=("X",),
    )


@case("sequence_conv")
def _sequence_conv():
    rng = R(647)
    x = _mix(rng, 2, 5, 3)
    w = _mix(rng, 9, 4) * 0.3

    def oracle(ins, a):
        xx, ww = ins["X"][0], ins["Filter"][0]
        xp = np.pad(xx, [(0, 0), (1, 1), (0, 0)])
        ctx = np.concatenate([xp[:, j:j + 5] for j in range(3)], axis=-1)
        return {"Out": [f32(np.einsum("btc,cf->btf", ctx, ww))]}

    return OpTest(
        "sequence_conv", {"X": x, "Filter": w}, oracle,
        attrs={"contextLength": 3, "contextStart": -1},
        grad=("X", "Filter"), tol=1e-4,
    )


@case("sequence_pad")
def _sequence_pad():
    x, ln = _seq_x(R(653))
    return OpTest(
        "sequence_pad", {"X": x, "Length": ln},
        lambda ins, a: {"Out": [ins["X"][0]], "Length": [ins["Length"][0]]},
        outputs={"Out": 1, "Length": 1},
    )


@case("sequence_unpad")
def _sequence_unpad():
    x, ln = _seq_x(R(659))

    def oracle(ins, a):
        xx, ll = ins["X"][0].copy(), ins["Length"][0]
        for i in range(3):
            xx[i, ll[i]:] = 0
        return {"Out": [xx]}

    return OpTest("sequence_unpad", {"X": x, "Length": ln}, oracle, grad=("X",))


@case("edit_distance")
def _edit_distance():
    hyp = np.asarray([[1, 2, 3, 0], [4, 4, 4, 4]], np.int64)
    ref = np.asarray([[1, 3, 3], [4, 5, 6]], np.int64)
    hlen = _lens(3, 4)
    rlen = _lens(3, 3)

    # dist(123, 133)=1; dist(4444, 456)=3
    def oracle(ins, a):
        return {"Out": [f32([[1.0], [3.0]])]}

    return OpTest(
        "edit_distance",
        {"Hyps": hyp, "Refs": ref, "HypsLength": hlen, "RefsLength": rlen},
        oracle, attrs={"normalized": False},
        outputs={"Out": 1, "SequenceNum": 1},
    )


def _np_lstm(x, w, bias, lens):
    b, t, h4 = x.shape
    h = h4 // 4
    sig = lambda z: 1 / (1 + np.exp(-z))
    hp = np.zeros((b, h), np.float32)
    cp = np.zeros((b, h), np.float32)
    hs = np.zeros((b, t, h), np.float32)
    cs = np.zeros((b, t, h), np.float32)
    for i in range(t):
        g = x[:, i] + hp @ w + bias.reshape(-1)
        c_t, i_t, f_t, o_t = np.split(g, 4, -1)
        c = np.tanh(c_t) * sig(i_t) + cp * sig(f_t)
        hh = sig(o_t) * np.tanh(c)
        keep = (i < lens)[:, None]
        hh = np.where(keep, hh, hp)
        c = np.where(keep, c, cp)
        hs[:, i], cs[:, i] = hh, c
        hp, cp = hh, c
    return f32(hs), f32(cs)


@case("lstm")
def _lstm():
    rng = R(661)
    b, t, h = 2, 3, 4
    x = _mix(rng, b, t, 4 * h) * 0.5
    w = _mix(rng, h, 4 * h) * 0.3
    bias = _mix(rng, 1, 4 * h) * 0.1
    lens = _lens(2, 3)

    def oracle(ins, a):
        hs, cs = _np_lstm(ins["Input"][0], ins["Weight"][0], ins["Bias"][0],
                          ins["Length"][0])
        return {"Hidden": [hs], "Cell": [cs]}

    return OpTest(
        "lstm", {"Input": x, "Weight": w, "Bias": bias, "Length": lens},
        oracle, outputs={"Hidden": 1, "Cell": 1},
        grad=("Input", "Weight"), tol=1e-4, grad_tol=2e-2,
    )


def _np_gru(x, w, bias, lens, origin=False):
    b, t, h3 = x.shape
    h = h3 // 3
    sig = lambda z: 1 / (1 + np.exp(-z))
    hp = np.zeros((b, h), np.float32)
    hs = np.zeros((b, t, h), np.float32)
    for i in range(t):
        g_ur = x[:, i, :2 * h] + hp @ w[:, :2 * h] + bias.reshape(-1)[:2 * h]
        u, r = sig(g_ur[:, :h]), sig(g_ur[:, h:])
        cand = np.tanh(x[:, i, 2 * h:] + (r * hp) @ w[:, 2 * h:] + bias.reshape(-1)[2 * h:])
        hh = u * hp + (1 - u) * cand if origin else (1 - u) * hp + u * cand
        keep = (i < lens)[:, None]
        hh = np.where(keep, hh, hp)
        hs[:, i] = hh
        hp = hh
    return f32(hs)


@case("gru")
def _gru():
    rng = R(673)
    b, t, h = 2, 3, 4
    x = _mix(rng, b, t, 3 * h) * 0.5
    w = _mix(rng, h, 3 * h) * 0.3
    bias = _mix(rng, 1, 3 * h) * 0.1
    lens = _lens(2, 3)

    def oracle(ins, a):
        return {"Hidden": [_np_gru(ins["Input"][0], ins["Weight"][0],
                                   ins["Bias"][0], ins["Length"][0])]}

    return OpTest(
        "gru", {"Input": x, "Weight": w, "Bias": bias, "Length": lens},
        oracle, outputs={"Hidden": 1},
        grad=("Input", "Weight"), tol=1e-4, grad_tol=2e-2,
    )


@case("linear_chain_crf")
def _crf():
    rng = R(677)
    b, t, d = 2, 4, 3
    em = _mix(rng, b, t, d)
    trans = _mix(rng, d + 2, d) * 0.5
    label = rng.randint(0, d, (b, t)).astype(np.int64)
    lens = _lens(3, 4)

    def oracle(ins, a):
        e, tr_all, lbl, ll = (ins["Emission"][0], ins["Transition"][0],
                              ins["Label"][0], ins["Length"][0])
        start, stop, tr = tr_all[0], tr_all[1], tr_all[2:]
        out = np.zeros((b, 1), np.float32)
        import itertools

        for i in range(b):
            n = ll[i]
            paths = []
            for path in itertools.product(range(d), repeat=int(n)):
                s = start[path[0]] + stop[path[-1]]
                s += sum(e[i, j, path[j]] for j in range(n))
                s += sum(tr[path[j], path[j + 1]] for j in range(n - 1))
                paths.append(s)
            logz = np.log(np.sum(np.exp(np.asarray(paths))))
            g = start[lbl[i, 0]] + stop[lbl[i, n - 1]]
            g += sum(e[i, j, lbl[i, j]] for j in range(n))
            g += sum(tr[lbl[i, j], lbl[i, j + 1]] for j in range(n - 1))
            out[i, 0] = logz - g
        return {"LogLikelihood": [out]}

    return OpTest(
        "linear_chain_crf",
        {"Emission": em, "Transition": trans, "Label": label, "Length": lens},
        oracle, outputs={"LogLikelihood": 1},
        grad=("Emission", "Transition"), tol=1e-4, grad_tol=2e-2,
    )


@case("crf_decoding")
def _crf_decoding():
    rng = R(683)
    b, t, d = 2, 3, 3
    em = _mix(rng, b, t, d)
    trans = _mix(rng, d + 2, d) * 0.5
    lens = _lens(2, 3)

    def oracle(ins, a):
        e, tr_all, ll = ins["Emission"][0], ins["Transition"][0], ins["Length"][0]
        start, stop, tr = tr_all[0], tr_all[1], tr_all[2:]
        import itertools

        out = np.zeros((b, t), np.int64)
        for i in range(b):
            n = ll[i]
            best, best_s = None, -np.inf
            for path in itertools.product(range(d), repeat=int(n)):
                s = start[path[0]] + stop[path[-1]]
                s += sum(e[i, j, path[j]] for j in range(n))
                s += sum(tr[path[j], path[j + 1]] for j in range(n - 1))
                if s > best_s:
                    best, best_s = path, s
            out[i, :n] = best
        return {"ViterbiPath": [out]}

    return OpTest(
        "crf_decoding",
        {"Emission": em, "Transition": trans, "Length": lens},
        oracle, outputs={"ViterbiPath": 1},
    )


@case("warpctc")
def _warpctc():
    rng = R(691)
    b, t, c, l = 2, 5, 4, 2
    logits = _mix(rng, b, t, c)
    label = rng.randint(1, c, (b, l)).astype(np.int32)
    tlen = _lens(5, 4)
    llen = _lens(2, 1)

    def oracle(ins, a):
        import itertools

        lg, lb = ins["Logits"][0], ins["Label"][0]
        tl, ll = ins["LogitsLength"][0], ins["LabelLength"][0]
        lp = np.log(_softmax(lg))
        out = np.zeros((b, 1), np.float32)
        for i in range(b):
            n, m = int(tl[i]), int(ll[i])
            target = list(lb[i, :m])
            total = -np.inf
            # brute force: all alignments of length n that collapse to target
            for ali in itertools.product(range(c), repeat=n):
                col = []
                prev = None
                for s in ali:
                    if s != 0 and s != prev:
                        col.append(s)
                    prev = s
                if col == target:
                    sc = sum(lp[i, j, ali[j]] for j in range(n))
                    total = np.logaddexp(total, sc)
            out[i, 0] = -total
        return {"Loss": [out]}

    return OpTest(
        "warpctc",
        {"Logits": logits, "Label": label, "LogitsLength": tlen, "LabelLength": llen},
        oracle, attrs={"blank": 0}, outputs={"Loss": 1},
        grad=("Logits",), tol=1e-4, grad_tol=2e-2,
    )


@case("beam_search")
def _beam_search():
    # B=1, W=2, V=4: hand-checked one step
    pre_ids = np.asarray([[1], [2]], np.int64)
    pre_scores = f32([[-0.5], [-1.0]])
    scores = f32([[-1.0, -2.0, -0.1, -3.0], [-0.2, -0.4, -5.0, -0.6]])

    def oracle(ins, a):
        # candidates: beam0: -0.5 + scores[0], beam1: -1.0 + scores[1]
        # beam0: [-1.5, -2.5, -0.6, -3.5]; beam1: [-1.2, -1.4, -6.0, -1.6]
        # top2 = -0.6 (b0, tok2), -1.2 (b1, tok0)
        return {
            "selected_ids": [np.asarray([[2], [0]], np.int64)],
            "selected_scores": [f32([[-0.6], [-1.2]])],
            "parent_idx": [np.asarray([0, 1], np.int32)],
        }

    return OpTest(
        "beam_search",
        {"pre_ids": pre_ids, "pre_scores": pre_scores, "scores": scores},
        oracle, attrs={"beam_size": 2, "end_id": 3},
        outputs={"selected_ids": 1, "selected_scores": 1, "parent_idx": 1},
    )


@case("cos_sim")
def _cos_sim():
    rng = R(761)
    x, y = _mix(rng, 4, 6), _mix(rng, 4, 6)

    def oracle(ins, a):
        xx, yy = ins["X"][0], ins["Y"][0]
        xn = np.linalg.norm(xx, axis=1, keepdims=True)
        yn = np.linalg.norm(yy, axis=1, keepdims=True)
        dot_ = (xx * yy).sum(1, keepdims=True)
        return {"Out": [f32(dot_ / (xn * yn))], "XNorm": [f32(xn)],
                "YNorm": [f32(yn)]}

    return OpTest(
        "cos_sim", {"X": x, "Y": y}, oracle,
        outputs={"Out": 1, "XNorm": 1, "YNorm": 1}, grad=("X", "Y"),
    )


# ---- detection ops ---------------------------------------------------------


def _np_iou(x, y):
    ax = (x[:, 2] - x[:, 0]) * (x[:, 3] - x[:, 1])
    ay = (y[:, 2] - y[:, 0]) * (y[:, 3] - y[:, 1])
    out = np.zeros((x.shape[0], y.shape[0]), np.float32)
    for i in range(x.shape[0]):
        for j in range(y.shape[0]):
            iw = min(x[i, 2], y[j, 2]) - max(x[i, 0], y[j, 0])
            ih = min(x[i, 3], y[j, 3]) - max(x[i, 1], y[j, 1])
            inter = max(iw, 0) * max(ih, 0)
            u = ax[i] + ay[j] - inter
            out[i, j] = inter / u if u > 0 else 0.0
    return out


def _boxes(rng, n):
    xy = rng.rand(n, 2).astype(np.float32)
    wh = rng.rand(n, 2).astype(np.float32) * 0.5 + 0.05
    return np.concatenate([xy, xy + wh], 1)


@case("iou_similarity")
def _iou_sim():
    rng = R(741)
    return OpTest(
        "iou_similarity", {"X": _boxes(rng, 5), "Y": _boxes(rng, 3)},
        lambda ins, a: {"Out": [_np_iou(ins["X"][0], ins["Y"][0])]},
        tol=1e-5,
    )


@case("box_coder")
def _box_coder_roundtrip():
    rng = R(743)
    prior = _boxes(rng, 4)
    target = _boxes(rng, 3)
    var = np.asarray([0.1, 0.1, 0.2, 0.2], np.float32)

    def oracle(ins, a):
        p, t = ins["PriorBox"][0], ins["TargetBox"][0]
        pw = p[:, 2] - p[:, 0]; ph = p[:, 3] - p[:, 1]
        pcx = p[:, 0] + pw / 2; pcy = p[:, 1] + ph / 2
        tw = t[:, 2] - t[:, 0]; th = t[:, 3] - t[:, 1]
        tcx = t[:, 0] + tw / 2; tcy = t[:, 1] + th / 2
        out = np.zeros((t.shape[0], p.shape[0], 4), np.float32)
        for i in range(t.shape[0]):
            for j in range(p.shape[0]):
                out[i, j] = [
                    (tcx[i] - pcx[j]) / pw[j] / var[0],
                    (tcy[i] - pcy[j]) / ph[j] / var[1],
                    np.log(tw[i] / pw[j]) / var[2],
                    np.log(th[i] / ph[j]) / var[3],
                ]
        return {"OutputBox": [out]}

    return OpTest(
        "box_coder", {"PriorBox": prior, "TargetBox": target},
        oracle, attrs={"code_type": "encode_center_size",
                       "box_normalized": True,
                       "variance": [0.1, 0.1, 0.2, 0.2]},
        outputs={"OutputBox": 1}, tol=1e-4,
    )


@case("box_coder")
def _box_coder_decode_axis1():
    rng = R(769)
    prior = _boxes(rng, 3)      # aligns with tb dim 0 (axis=1)
    deltas = f32(rng.randn(3, 2, 4) * 0.1)

    def oracle(ins, a):
        p, t = ins["PriorBox"][0], ins["TargetBox"][0]
        pw = p[:, 2] - p[:, 0]; ph = p[:, 3] - p[:, 1]
        pcx = p[:, 0] + pw / 2; pcy = p[:, 1] + ph / 2
        out = np.zeros_like(t)
        for i in range(t.shape[0]):
            for j in range(t.shape[1]):
                d = t[i, j]
                cx = d[0] * pw[i] + pcx[i]
                cy = d[1] * ph[i] + pcy[i]
                w = np.exp(d[2]) * pw[i]
                h = np.exp(d[3]) * ph[i]
                out[i, j] = [cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2]
        return {"OutputBox": [f32(out)]}

    return OpTest(
        "box_coder", {"PriorBox": prior, "TargetBox": deltas},
        oracle, attrs={"code_type": "decode_center_size",
                       "box_normalized": True, "axis": 1},
        outputs={"OutputBox": 1}, tol=1e-4,
    )


@case("prior_box")
def _prior_box():
    rng = R(747)
    feat = f32(rng.rand(1, 8, 2, 3))
    img = f32(rng.rand(1, 3, 64, 96))

    def oracle(ins, a):
        h, w, ih, iw = 2, 3, 64, 96
        step_h, step_w = ih / h, iw / w
        shapes = [(20.0, 20.0), (20.0 * np.sqrt(2.0), 20.0 / np.sqrt(2.0)),
                  (np.sqrt(20.0 * 40.0), np.sqrt(20.0 * 40.0))]
        boxes = np.zeros((h, w, 3, 4), np.float32)
        for yy in range(h):
            for xx in range(w):
                cx = (xx + 0.5) * step_w
                cy = (yy + 0.5) * step_h
                for k, (bw, bh) in enumerate(shapes):
                    boxes[yy, xx, k] = [(cx - bw / 2) / iw, (cy - bh / 2) / ih,
                                        (cx + bw / 2) / iw, (cy + bh / 2) / ih]
        var = np.broadcast_to(
            np.asarray([0.1, 0.1, 0.2, 0.2], np.float32), boxes.shape
        ).copy()
        return {"Boxes": [boxes], "Variances": [var]}

    return OpTest(
        "prior_box", {"Input": feat, "Image": img}, oracle,
        attrs={"min_sizes": [20.0], "max_sizes": [40.0],
               "aspect_ratios": [2.0], "flip": False,
               "variances": [0.1, 0.1, 0.2, 0.2]},
        outputs={"Boxes": 1, "Variances": 1}, tol=1e-4,
    )


@case("yolo_box")
def _yolo_box():
    rng = R(751)
    n, p_, cls, h, w = 1, 2, 3, 2, 2
    x = f32(rng.randn(n, p_ * (5 + cls), h, w) * 0.5)
    img = np.asarray([[64, 96]], np.int32)

    def oracle(ins, a):
        sig = lambda z: 1 / (1 + np.exp(-z))
        xx = ins["X"][0].reshape(n, p_, 5 + cls, h, w)
        anchors = [10, 14, 23, 27]
        boxes = np.zeros((n, p_, h, w, 4), np.float32)
        scores = np.zeros((n, p_, h, w, cls), np.float32)
        for pi in range(p_):
            for yy in range(h):
                for xc in range(w):
                    t = xx[0, pi, :, yy, xc]
                    bx = (sig(t[0]) + xc) / w
                    by = (sig(t[1]) + yy) / h
                    bw = np.exp(t[2]) * anchors[2 * pi] / (32.0 * w)
                    bh = np.exp(t[3]) * anchors[2 * pi + 1] / (32.0 * h)
                    conf = sig(t[4])
                    b = [np.clip((bx - bw / 2) * 96, 0, 95),
                         np.clip((by - bh / 2) * 64, 0, 63),
                         np.clip((bx + bw / 2) * 96, 0, 95),
                         np.clip((by + bh / 2) * 64, 0, 63)]
                    if conf > 0.5:
                        boxes[0, pi, yy, xc] = b
                        scores[0, pi, yy, xc] = sig(t[5:]) * conf
        return {"Boxes": [boxes.reshape(n, -1, 4)],
                "Scores": [scores.reshape(n, -1, cls)]}

    return OpTest(
        "yolo_box", {"X": x, "ImgSize": img}, oracle,
        attrs={"anchors": [10, 14, 23, 27], "class_num": cls,
               "conf_thresh": 0.5, "downsample_ratio": 32},
        outputs={"Boxes": 1, "Scores": 1}, tol=1e-4,
    )


@case("roi_align")
def _roi_align():
    rng = R(757)
    x = f32(rng.rand(2, 3, 8, 8))
    rois = f32([[0.0, 0.0, 4.0, 4.0], [2.0, 2.0, 6.0, 6.0]])
    bidx = np.asarray([0, 1], np.int32)

    def oracle(ins, a):
        xx, rr = ins["X"][0], ins["ROIs"][0]
        ph = pw = 2
        ratio = 2
        out = np.zeros((2, 3, ph, pw), np.float32)

        def bil(img, yy, xx_):
            yy = np.clip(yy, 0, 7); xx_ = np.clip(xx_, 0, 7)
            y0, x0 = int(np.floor(yy)), int(np.floor(xx_))
            y1, x1 = min(y0 + 1, 7), min(x0 + 1, 7)
            ly, lx = yy - y0, xx_ - x0
            return (img[:, y0, x0] * (1 - ly) * (1 - lx) +
                    img[:, y0, x1] * (1 - ly) * lx +
                    img[:, y1, x0] * ly * (1 - lx) +
                    img[:, y1, x1] * ly * lx)

        for ri, (roi, b) in enumerate(zip(rr, [0, 1])):
            rw = max(roi[2] - roi[0], 1.0); rh = max(roi[3] - roi[1], 1.0)
            bw, bh = rw / pw, rh / ph
            for i in range(ph):
                for j in range(pw):
                    acc = np.zeros(3, np.float32)
                    for si in range(ratio):
                        for sj in range(ratio):
                            yy = roi[1] + (i + (si + 0.5) / ratio) * bh
                            xx_ = roi[0] + (j + (sj + 0.5) / ratio) * bw
                            acc += bil(xx[b], yy, xx_)
                    out[ri, :, i, j] = acc / (ratio * ratio)
        return {"Out": [out]}

    return OpTest(
        "roi_align", {"X": x, "ROIs": rois, "BatchIndex": bidx}, oracle,
        attrs={"pooled_height": 2, "pooled_width": 2, "spatial_scale": 1.0,
               "sampling_ratio": 2},
        grad=("X",), tol=1e-4, grad_tol=2e-2,
    )


# ---- fake quantization -----------------------------------------------------


def _np_qdq(x, scale, bits=8):
    qmax = 2 ** (bits - 1) - 1
    s = np.maximum(scale, 1e-8)
    return np.clip(np.round(x / s * qmax), -qmax, qmax) * s / qmax


@case("fake_quantize_dequantize_abs_max")
def _fqdq_absmax():
    x = _mix(R(719), 3, 4)

    def oracle(ins, a):
        s = np.abs(ins["X"][0]).max()
        return {"Out": [f32(_np_qdq(ins["X"][0], s))], "OutScale": [f32([s])]}

    return OpTest(
        "fake_quantize_dequantize_abs_max", {"X": x}, oracle,
        attrs={"bit_length": 8}, outputs={"Out": 1, "OutScale": 1}, tol=1e-5,
    )


@case("fake_quantize_dequantize_moving_average_abs_max")
def _fqdq_ema():
    rng = R(727)
    x = _mix(rng, 3, 4)
    accum, state = f32([0.7]), f32([1.0])

    def oracle(ins, a):
        na = 0.9 * ins["InAccum"][0][0] + np.abs(ins["X"][0]).max()
        ns = 0.9 * ins["InState"][0][0] + 1.0
        s = na / ns
        return {"Out": [f32(_np_qdq(ins["X"][0], s))],
                "OutAccum": [f32([na])], "OutState": [f32([ns])],
                "OutScale": [f32([s])]}

    return OpTest(
        "fake_quantize_dequantize_moving_average_abs_max",
        {"X": x, "InAccum": accum, "InState": state}, oracle,
        attrs={"bit_length": 8, "moving_rate": 0.9},
        outputs={"Out": 1, "OutAccum": 1, "OutState": 1, "OutScale": 1},
        tol=1e-5,
    )


@case("fake_quant_dequant_fixed_scale")
def _fqdq_fixed():
    x = _mix(R(733), 3, 4)
    return OpTest(
        "fake_quant_dequant_fixed_scale", {"X": x},
        lambda ins, a: {"Out": [f32(_np_qdq(ins["X"][0], 1.5))]},
        attrs={"bit_length": 8, "scale": 1.5}, tol=1e-5,
    )


# ---- breadth ops (vision_ops.py / misc_ops.py) ----------------------------

unary("selu", lambda x, a: np.where(
    x > 0, x, 1.6732632423543772 * (np.exp(x) - 1.0)) * 1.0507009873554805)
unary("brelu", lambda x, a: np.clip(x, 1.0, 3.0),
      attrs={"t_min": 1.0, "t_max": 3.0}, inp=_pos, grad=False)
unary("soft_relu", lambda x, a: np.log1p(np.exp(np.clip(x, -40.0, 40.0))))
unary("stanh", lambda x, a: 1.7159 * np.tanh(0.67 * x))


@case("multiplex")
def _multiplex():
    rng = R(61)
    xs = [_mix(rng, 4, 3), _mix(rng, 4, 3), _mix(rng, 4, 3)]
    ids = np.asarray([[2], [0], [1], [0]], np.int32)

    def oracle(ins, a):
        stacked = np.stack(ins["X"])
        sel = ins["Ids"][0].reshape(-1)
        return {"Out": [stacked[sel, np.arange(4)]]}

    return OpTest("multiplex", {"X": xs, "Ids": ids}, oracle, grad=("X",))


@case("mean_iou")
def _mean_iou():
    pred = np.asarray([0, 1, 1, 2, 2, 2], np.int32)
    lab = np.asarray([0, 1, 2, 2, 2, 1], np.int32)

    def oracle(ins, a):
        nc = 3
        inter = np.zeros(nc)
        union = np.zeros(nc)
        for c in range(nc):
            p, l = pred == c, lab == c
            inter[c] = (p & l).sum()
            union[c] = (p | l).sum()
        iou = np.where(union > 0, inter / np.maximum(union, 1), 0)
        return {"OutMeanIou": [np.float32(iou[union > 0].mean())]}

    return OpTest(
        "mean_iou", {"Predictions": pred, "Labels": lab}, oracle,
        attrs={"num_classes": 3},
        outputs={"OutMeanIou": 1, "OutWrong": 1, "OutCorrect": 1},
    )


@case("pixel_shuffle")
def _pixel_shuffle():
    rng = R(62)
    x = _mix(rng, 2, 8, 3, 3)

    def oracle(ins, a):
        n, c, h, w = ins["X"][0].shape
        r, oc = 2, c // 4
        t = ins["X"][0].reshape(n, oc, r, r, h, w).transpose(0, 1, 4, 2, 5, 3)
        return {"Out": [t.reshape(n, oc, h * r, w * r)]}

    return OpTest("pixel_shuffle", {"X": x}, oracle,
                  attrs={"upscale_factor": 2}, grad=("X",))


@case("space_to_depth")
def _space_to_depth():
    rng = R(63)
    x = _mix(rng, 2, 3, 4, 4)

    def oracle(ins, a):
        n, c, h, w = ins["X"][0].shape
        bs = 2
        t = ins["X"][0].reshape(n, c, h // bs, bs, w // bs, bs)
        t = t.transpose(0, 3, 5, 1, 2, 4)
        return {"Out": [t.reshape(n, c * bs * bs, h // bs, w // bs)]}

    return OpTest("space_to_depth", {"X": x}, oracle,
                  attrs={"blocksize": 2}, grad=("X",))


@case("shuffle_channel")
def _shuffle_channel():
    rng = R(64)
    x = _mix(rng, 2, 6, 2, 2)

    def oracle(ins, a):
        n, c, h, w = ins["X"][0].shape
        g = 3
        return {"Out": [ins["X"][0].reshape(n, g, c // g, h, w)
                        .swapaxes(1, 2).reshape(n, c, h, w)]}

    return OpTest("shuffle_channel", {"X": x}, oracle,
                  attrs={"group": 3}, grad=("X",))


@case("temporal_shift")
def _temporal_shift():
    rng = R(65)
    x = _mix(rng, 4, 8, 2, 2)  # N*T with T=2

    def oracle(ins, a):
        t = 2
        nt, c, h, w = ins["X"][0].shape
        x5 = ins["X"][0].reshape(nt // t, t, c, h, w)
        c1, c2 = c // 4, c // 2
        out = np.zeros_like(x5)
        out[:, :-1, :c1] = x5[:, 1:, :c1]
        out[:, 1:, c1:c2] = x5[:, :-1, c1:c2]
        out[:, :, c2:] = x5[:, :, c2:]
        return {"Out": [out.reshape(nt, c, h, w)]}

    return OpTest("temporal_shift", {"X": x}, oracle,
                  attrs={"seg_num": 2, "shift_ratio": 0.25}, grad=("X",))


@case("row_conv")
def _row_conv():
    rng = R(66)
    x = _mix(rng, 2, 5, 3)
    f = _mix(rng, 3, 3)

    def oracle(ins, a):
        xx, ff = ins["X"][0], ins["Filter"][0]
        pad = np.pad(xx, [(0, 0), (0, ff.shape[0] - 1), (0, 0)])
        out = np.zeros_like(xx)
        for k in range(ff.shape[0]):
            out += pad[:, k : k + xx.shape[1]] * ff[k][None, None, :]
        return {"Out": [out]}

    return OpTest("row_conv", {"X": x, "Filter": f}, oracle,
                  grad=("X", "Filter"))


@case("bilinear_tensor_product")
def _bilinear_tensor_product():
    rng = R(67)
    x, y = _mix(rng, 3, 4), _mix(rng, 3, 5)
    w = _mix(rng, 2, 4, 5)
    b = _mix(rng, 1, 2)

    def oracle(ins, a):
        out = np.einsum("bi,kij,bj->bk", ins["X"][0], ins["Weight"][0],
                        ins["Y"][0]) + ins["Bias"][0]
        return {"Out": [out.astype(np.float32)]}

    return OpTest(
        "bilinear_tensor_product",
        {"X": x, "Y": y, "Weight": w, "Bias": b}, oracle,
        grad=("X", "Y", "Weight"),
    )


@case("lrn")
def _lrn():
    rng = R(68)
    x = _mix(rng, 2, 6, 3, 3)

    def oracle(ins, a):
        xx = ins["X"][0]
        n, k, alpha, beta = 5, 1.0, 1e-4, 0.75
        sq = xx * xx
        half = n // 2
        padded = np.pad(sq, [(0, 0), (half, n - 1 - half), (0, 0), (0, 0)])
        win = sum(padded[:, i : i + xx.shape[1]] for i in range(n))
        return {"Out": [(xx / (k + alpha * win) ** beta).astype(np.float32)]}

    return OpTest("lrn", {"X": x}, oracle,
                  outputs={"Out": 1, "MidOut": 1}, grad=("X",))


@case("pool3d")
def _pool3d():
    rng = R(69)
    x = _mix(rng, 1, 2, 4, 4, 4)

    def oracle(ins, a):
        xx = ins["X"][0]
        n, c, d, h, w = xx.shape
        out = xx.reshape(n, c, d // 2, 2, h // 2, 2, w // 2, 2).max(
            axis=(3, 5, 7))
        return {"Out": [out]}

    return OpTest("pool3d", {"X": x}, oracle,
                  attrs={"pooling_type": "max", "ksize": [2, 2, 2],
                         "strides": [2, 2, 2]}, grad=("X",))


@case("unfold")
def _unfold():
    rng = R(70)
    x = _mix(rng, 1, 2, 4, 4)

    def oracle(ins, a):
        xx = ins["X"][0]
        n, c, h, w = xx.shape
        cols = []
        for i in range(h - 1):
            for j in range(w - 1):
                cols.append(xx[:, :, i : i + 2, j : j + 2].reshape(n, -1))
        return {"Y": [np.stack(cols, axis=-1)]}

    return OpTest("unfold", {"X": x}, oracle,
                  attrs={"kernel_sizes": [2, 2]},
                  outputs={"Y": 1}, grad=("X",))


@case("im2sequence")
def _im2sequence():
    rng = R(71)
    x = _mix(rng, 1, 2, 3, 3)

    def oracle(ins, a):
        xx = ins["X"][0]
        n, c, h, w = xx.shape
        rows = []
        for i in range(h - 1):
            for j in range(w - 1):
                rows.append(xx[:, :, i : i + 2, j : j + 2].reshape(n, -1))
        return {"Out": [np.stack(rows, axis=1)]}

    return OpTest("im2sequence", {"X": x}, oracle,
                  attrs={"kernels": [2, 2]}, grad=("X",))


@case("sequence_enumerate")
def _sequence_enumerate():
    x = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    ln = np.asarray([3, 4], np.int32)

    def oracle(ins, a):
        out = np.zeros((2, 4, 2), np.int32)
        for b in range(2):
            for t in range(4):
                for k in range(2):
                    out[b, t, k] = x[b, t + k] if t + k < ln[b] else 0
        return {"Out": [out]}

    return OpTest("sequence_enumerate", {"X": x, "Length": ln}, oracle,
                  attrs={"win_size": 2, "pad_value": 0})


@case("sequence_slice")
def _sequence_slice():
    rng = R(72)
    x = _mix(rng, 2, 5, 3)
    off = np.asarray([1, 2], np.int32)
    ln = np.asarray([3, 2], np.int32)

    def oracle(ins, a):
        out = np.zeros_like(x)
        for b in range(2):
            out[b, : ln[b]] = x[b, off[b] : off[b] + ln[b]]
        return {"Out": [out]}

    return OpTest("sequence_slice", {"X": x, "Offset": off, "Length": ln},
                  oracle, outputs={"Out": 1, "OutLength": 1}, grad=("X",))


@case("sequence_reshape")
def _sequence_reshape():
    rng = R(73)
    x = _mix(rng, 2, 4, 6)

    def oracle(ins, a):
        return {"Out": [ins["X"][0].reshape(2, 8, 3)]}

    return OpTest("sequence_reshape", {"X": x}, oracle,
                  attrs={"new_dim": 3}, grad=("X",))


@case("sequence_scatter")
def _sequence_scatter():
    rng = R(74)
    x = _mix(rng, 2, 6)
    ids = np.asarray([[0, 2, 2], [5, 1, 0]], np.int32)
    upd = _mix(rng, 2, 3)
    ln = np.asarray([3, 2], np.int32)

    def oracle(ins, a):
        out = x.copy()
        for b in range(2):
            for s in range(3):
                if s < ln[b]:
                    out[b, ids[b, s]] += upd[b, s]
        return {"Out": [out]}

    return OpTest("sequence_scatter",
                  {"X": x, "Ids": ids, "Updates": upd, "Length": ln},
                  oracle, grad=("X",))


@case("sequence_concat")
def _sequence_concat():
    rng = R(75)
    a_ = _mix(rng, 2, 3, 2)
    b_ = _mix(rng, 2, 2, 2)
    lens = np.asarray([[2, 3], [1, 2]], np.int32)  # stacked [k, B] -> flat

    def oracle(ins, at):
        out = np.zeros((2, 5, 2), np.float32)
        newlen = np.zeros(2, np.int32)
        for b in range(2):
            pos = 0
            for x, ln in ((a_, lens[0]), (b_, lens[1])):
                out[b, pos : pos + ln[b]] = x[b, : ln[b]]
                pos += ln[b]
            newlen[b] = pos
        return {"Out": [out], "Length": [newlen]}

    return OpTest("sequence_concat",
                  {"X": [a_, b_], "Length": lens.reshape(-1)},
                  oracle, outputs={"Out": 1, "Length": 1}, grad=("X",))


@case("gather_tree")
def _gather_tree():
    # T=3, B=1, W=2 hand-traced beam backtrace
    ids = np.asarray([[[1, 2]], [[3, 4]], [[5, 6]]], np.int64)
    parents = np.asarray([[[0, 0]], [[0, 0]], [[1, 0]]], np.int64)

    def oracle(ins, a):
        # final beams: w0 traces parent 1 at t2 -> ids path [1,4,5];
        # w1 traces parent 0 -> [1,3,6]
        return {"Out": [np.asarray([[[1, 1]], [[4, 3]], [[5, 6]]], np.int64)]}

    return OpTest("gather_tree", {"Ids": ids, "Parents": parents}, oracle)


unary("tanh_shrink", lambda x, a: x - np.tanh(x))


@case("diag_embed")
def _diag_embed():
    rng = R(77)
    x = _mix(rng, 2, 4)

    def oracle(ins, a):
        out = np.zeros((2, 4, 4), np.float32)
        for b in range(2):
            np.fill_diagonal(out[b], ins["X"][0][b])
        return {"Out": [out]}

    return OpTest("diag_embed", {"X": x}, oracle, grad=("X",))


@case("histogram")
def _histogram():
    x = np.asarray([0.1, 0.2, 0.55, 0.9, 0.95, 2.0], np.float32)

    def oracle(ins, a):
        return {"Out": [np.histogram(x, bins=4, range=(0, 1))[0]
                        .astype(np.int32)]}

    return OpTest("histogram", {"X": x}, oracle,
                  attrs={"bins": 4, "min": 0.0, "max": 1.0})


@case("nonzero_static")
def _nonzero_static():
    x = np.asarray([[0, 3, 0], [2, 0, 1]], np.float32)

    def oracle(ins, a):
        idx = np.argwhere(x != 0).astype(np.int32)
        pad = np.full((x.size - len(idx), 2), -1, np.int32)
        return {"Out": [np.concatenate([idx, pad])],
                "Count": [np.int32(len(idx))]}

    return OpTest("nonzero_static", {"X": x}, oracle,
                  outputs={"Out": 1, "Count": 1})


# ---- decoder blocks (ops/decoder_ops.py) ----------------------------------
@case("rms_norm")
def _rms_norm():
    rng = R(461)
    x, scale = _mix(rng, 3, 8), _pos(rng, 4)  # two groups ("heads") of 4

    def oracle(ins, a):
        xx = ins["X"][0].reshape(3, 2, 4)
        y = xx / np.sqrt((xx * xx).mean(-1, keepdims=True) + 1e-5) * ins["Scale"][0]
        return {"Y": [f32(y.reshape(3, 8))]}

    return OpTest("rms_norm", {"X": x, "Scale": scale}, oracle,
                  attrs={"epsilon": 1e-5}, outputs={"Y": 1},
                  grad=("X", "Scale"), tol=1e-5, grad_tol=2e-2)


@case("rope")
def _rope():
    x = _mix(R(463), 2, 5, 8)

    def oracle(ins, a):
        xx = ins["X"][0].reshape(2, 5, 2, 2, 2)  # heads of 4: pairs (i, i+2)
        ang = np.arange(5)[:, None] * 100.0 ** (-np.arange(2) * 2.0 / 4)
        c, s = np.cos(ang)[None, :, None, :], np.sin(ang)[None, :, None, :]
        y = np.stack([xx[..., 0, :] * c - xx[..., 1, :] * s,
                      xx[..., 1, :] * c + xx[..., 0, :] * s], axis=-2)
        return {"Out": [f32(y.reshape(2, 5, 8))]}

    return OpTest("rope", {"X": x}, oracle,
                  attrs={"head_dim": 4, "theta": 100.0}, grad=("X",),
                  tol=1e-5)


@case("short_conv")
def _short_conv():
    rng = R(467)
    x = _mix(rng, 2, 6, 4)
    w_in, taps, w_out = _mix(rng, 4, 12), _mix(rng, 3, 4), _mix(rng, 4, 4)

    def oracle(ins, a):
        proj = ins["X"][0] @ ins["InW"][0]
        bg, cg, u = proj[..., :4], proj[..., 4:8], proj[..., 8:]
        bu = np.pad(bg * u, ((0, 0), (2, 0), (0, 0)))  # zeros before t = 0
        t = ins["Filter"][0]
        c = sum(t[j] * bu[:, j:j + 6] for j in range(3))
        return {"Out": [f32((cg * c) @ ins["OutW"][0])]}

    return OpTest("short_conv",
                  {"X": x, "InW": w_in, "Filter": taps, "OutW": w_out},
                  oracle, grad=("X", "InW", "Filter", "OutW"), tol=1e-4,
                  grad_tol=2e-2)


@case("swiglu_ffn")
def _swiglu_ffn():
    rng = R(479)
    x, w1, w3, w2 = _mix(rng, 3, 4), _mix(rng, 4, 6), _mix(rng, 4, 6), _mix(rng, 6, 4)

    def oracle(ins, a):
        xx = ins["X"][0]
        a1 = xx @ ins["W1"][0]
        return {"Out": [f32((a1 / (1 + np.exp(-a1)) * (xx @ ins["W3"][0]))
                            @ ins["W2"][0])]}

    return OpTest("swiglu_ffn", {"X": x, "W1": w1, "W3": w3, "W2": w2}, oracle,
                  attrs={"remat": True}, grad=("X", "W1", "W3", "W2"),
                  tol=1e-4, grad_tol=2e-2)


@case("shared_expert")
def _shared_expert():
    rng = R(487)
    x, w1, w3, w2 = _mix(rng, 3, 4), _mix(rng, 4, 6), _mix(rng, 4, 6), _mix(rng, 6, 4)

    def oracle(ins, a):
        xx = ins["X"][0]
        a1 = xx @ ins["W1"][0]
        return {"Out": [f32((a1 / (1 + np.exp(-a1)) * (xx @ ins["W3"][0]))
                            @ ins["W2"][0])]}

    return OpTest("shared_expert", {"X": x, "W1": w1, "W3": w3, "W2": w2},
                  oracle, attrs={"remat": False},
                  grad=("X", "W1", "W3", "W2"), tol=1e-4, grad_tol=2e-2)


@case("mhc_pre")
def _mhc_pre():
    rng = R(491)
    x, h = _mix(rng, 2, 3, 12), _pos(rng, 2, 3, 3)  # three streams of 4

    def oracle(ins, a):
        xs = ins["X"][0].reshape(2, 3, 3, 4)
        return {"Out": [f32(np.einsum("bsn,bsnc->bsc", ins["HPre"][0], xs))]}

    return OpTest("mhc_pre", {"X": x, "HPre": h}, oracle,
                  attrs={"streams": 3}, grad=("X", "HPre"), tol=1e-5,
                  grad_tol=2e-2)


@case("mhc_post")
def _mhc_post():
    rng = R(499)
    x, y = _mix(rng, 2, 3, 12), _mix(rng, 2, 3, 4)
    h_res, h_post = _pos(rng, 2, 3, 9), _pos(rng, 2, 3, 3)

    def oracle(ins, a):
        xs = ins["X"][0].reshape(2, 3, 3, 4)
        mixed = np.einsum("bsij,bsjc->bsic",
                          ins["HRes"][0].reshape(2, 3, 3, 3), xs)
        mixed = mixed + ins["HPost"][0][..., None] * ins["Y"][0][:, :, None]
        return {"Out": [f32(mixed.reshape(2, 3, 12))]}

    return OpTest("mhc_post",
                  {"X": x, "Y": y, "HRes": h_res, "HPost": h_post}, oracle,
                  grad=("X", "Y", "HRes", "HPost"), tol=1e-5, grad_tol=2e-2)


# ---------------------------------------------------------------------------
# exemptions: ops whose contract is verified elsewhere or is stochastic
# ---------------------------------------------------------------------------

EXEMPT = {
    # numerics-observability reduction (ISSUE 12): emitter checked
    # against numpy (nan/inf counts, finite max-abs/l2) in
    # tests/test_numerics.py::test_tensor_stats_emitter_matches_numpy
    "tensor_stats": "test_numerics.py",
    # collectives need a mesh + axis env; numerics are checked against
    # numpy on an 8-device virtual mesh in tests/test_collectives.py
    "c_allgather": "test_collectives.py",
    "c_allreduce_max": "test_collectives.py",
    "c_allreduce_min": "test_collectives.py",
    "c_allreduce_prod": "test_collectives.py",
    "c_allreduce_sum": "test_collectives.py",
    "c_broadcast": "test_collectives.py",
    "c_reducescatter": "test_collectives.py",
    "c_identity": "test_collectives.py",
    # comm bootstrap/sync ops are no-ops under XLA (PJRT owns streams);
    # exercised by every fleet/dryrun program in test_fleet.py
    "c_comm_init": "no-op under XLA; test_fleet.py",
    "c_comm_init_all": "no-op under XLA; test_fleet.py",
    "c_gen_nccl_id": "no-op under XLA; test_fleet.py",
    "c_sync_calc_stream": "no-op under XLA; test_fleet.py",
    "c_sync_comm_stream": "no-op under XLA; test_fleet.py",
    "c_wait_comm": "no-op under XLA; test_fleet.py",
    "c_wait_compute": "no-op under XLA; test_fleet.py",
    # side-effect ops (host print/assert callbacks): test_control_flow.py
    "print": "test_control_flow.py (passthrough + host print)",
    "assert": "test_control_flow.py (raises on false cond)",
    # control flow needs sub-block programs: tests/test_control_flow.py
    "cond": "test_control_flow.py",
    "while_loop": "test_control_flow.py",
    "recurrent": "sub-block scan; test_static_rnn_pyfunc.py (numpy oracle)",
    "py_func": "host callable in attrs; test_static_rnn_pyfunc.py",
    "select_input": "test_control_flow.py",
    # fused mega-ops have dedicated oracle suites
    "moe_ffn": "test_moe.py (numpy routing oracle, capacity, ep parity)",
    "moe_swiglu": "test_lfm2_ops.py (dense-loop oracle, shares add up, "
                  "nothing dropped, bias selects only)",
    "mla": "test_xing4_ops.py (the reference's sublayer, forward and "
           "gradients) + test_xing4_model.py (head shares add up)",
    "mhc_map": "test_xing4_ops.py (float64 numpy mappings, Sinkhorn after "
               "20 rounds and after 1, gradients against jax)",
    "mamba2": "test_nemotron_ops.py (the chunked scan against the float64 "
              "recurrence, forward and every gradient; the mixer against "
              "the reference's) + test_nemotron_model.py",
    "fused_encoder_stack": "test_bert.py (vs per-layer composition)",
    "fused_decoder_stack": "test_sequence_models.py (fused NMT stack "
                           "trains + stays causal)",
    "c_dcn_grad_sync": "test_dcn.py (two-level sync parity + DGC "
                       "oracles on the (dcn, dp) mesh)",
    "c_dcn_localsgd_sync": "test_dcn.py (LocalSGD consensus oracle on "
                           "the (dcn, dp) mesh)",
    "dcn_expand_param": "test_dcn.py (outer-optimizer state expansion)",
    "tree_conv": "test_tree_conv.py (numpy eta-coefficient oracle)",
    "fused_multihead_attention": "test_flash_attention.py + test_bert.py",
    "recompute_segment": "test_meta_optimizers.py (recompute)",
    # explicit grad kernels: exercised by check_grad of their forward op
    "dropout_grad": "via dropout case's check_grad",
    "argsort_grad": "via argsort case's check_grad",
    "top_k_grad": "via top_k case's check_grad",
    "top_k_v2_grad": "via top_k_v2 case's check_grad",
    # host parameter-server bridge: needs the global table registry and
    # host-side optimizer state; covered end to end in test_ps_embedding.py
    "distributed_lookup_table": "test_ps_embedding.py",
    # detection batch 2: numpy oracles through the executor in
    # tests/test_detection2.py (static-shape NMS/assignment contracts)
    "anchor_generator": "test_detection2.py (hand oracle)",
    "density_prior_box": "test_detection2.py",
    "box_clip": "test_detection2.py (hand oracle)",
    "box_decoder_and_assign": "test_detection2.py (zero-delta oracle)",
    "multiclass_nms": "test_detection2.py (suppression + padding)",
    "matrix_nms": "test_detection2.py (decay semantics)",
    "locality_aware_nms": "test_detection2.py (merge + NMS)",
    "target_assign": "test_detection2.py (hand oracle)",
    "bipartite_match": "test_detection2.py (greedy oracle)",
    "polygon_box_transform": "test_detection2.py (hand oracle)",
    "ctc_align": "test_detection2.py (collapse oracle)",
    "ssd_loss": "test_detection2.py (end-to-end training)",
    # detection batch 3 (proposals/ROI/yolo): tests/test_detection2.py
    "generate_proposals": "test_detection2.py (shapes/clip/NMS)",
    "rpn_target_assign": "test_detection2.py (budget + exact-match deltas)",
    "retinanet_target_assign": "test_detection2.py via rpn variant",
    "collect_fpn_proposals": "test_detection2.py",
    "distribute_fpn_proposals": "test_detection2.py (restore permutation)",
    "prroi_pool": "test_detection2.py (shape/finite)",
    "psroi_pool": "test_detection2.py (shape/finite)",
    "roi_perspective_transform": "test_detection2.py (identity-quad oracle)",
    "deformable_conv": "test_detection2.py (zero-offset == conv2d)",
    "deformable_psroi_pooling": "test_detection2.py via deformable_roi_pooling",
    "yolov3_loss": "test_detection2.py (end-to-end training)",
    # vision/misc breadth ops: numpy-oracle + semantics tests through the
    # executor live in tests/test_layers_breadth.py
    "conv3d_transpose": "test_layers_breadth.py (adjoint + identity oracle)",
    "bilinear_interp": "test_layers_breadth.py (corner/align oracle)",
    "nearest_interp": "test_layers_breadth.py (integer-upscale oracle)",
    "trilinear_interp": "test_layers_breadth.py",
    "linear_interp": "test_layers_breadth.py",
    "affine_grid": "test_layers_breadth.py (identity-theta oracle)",
    "grid_sampler": "test_layers_breadth.py (identity-grid oracle)",
    "roi_pool": "test_layers_breadth.py (hand-computed ROI oracle)",
    "spectral_norm": "test_layers_breadth.py (sigma_max vs numpy svd)",
    "data_norm": "test_layers_breadth.py (accumulator-stat oracle)",
    "unique": "test_layers_breadth.py (static-shape padding contract)",
    "unique_with_counts": "test_layers_breadth.py",
    "hash": "test_layers_breadth.py (determinism/range/spread)",
    "sampling_id": "test_layers_breadth.py (distribution check)",
    "randperm": "test_api20.py (permutation property; stochastic)",
    "precision_recall": "test_layers_breadth2.py (streaming states)",
    # stochastic draws: distribution checked in test_random_ops below
    "uniform_random": "test_random_ops",
    "gaussian_random": "test_random_ops",
    "truncated_gaussian_random": "test_random_ops",
    "dpsgd": "test_random_ops (noisy update; mean drift checked)",
}


# ---------------------------------------------------------------------------
# the tests
# ---------------------------------------------------------------------------


def test_coverage():
    registered = set(registry.registered_ops())
    # registry.get() caches lazily synthesized generic "<op>_grad" specs;
    # those are the vjp of an already-covered forward op, not independent
    # kernels. Keep only grad ops with their own explicit registration
    # (they appear in EXEMPT with a justification).
    registered -= {
        n for n in registered
        if n.endswith("_grad") and n[: -len("_grad")] in registered
        and n not in EXEMPT and n not in CASES
    }
    covered = set(CASES) | set(EXEMPT)
    missing = registered - covered
    assert not missing, f"ops with neither case nor exemption: {sorted(missing)}"
    double = set(CASES) & set(EXEMPT)
    assert not double, f"ops both cased and exempted: {sorted(double)}"
    stale = covered - registered
    assert not stale, f"cases/exemptions for unregistered ops: {sorted(stale)}"


_ALL = [(op, i) for op, fns in sorted(CASES.items()) for i in range(len(fns))]


@pytest.mark.parametrize("op_type,i", _ALL, ids=[f"{o}-{i}" for o, i in _ALL])
def test_op(op_type, i):
    CASES[op_type][i]().run()


def test_random_ops():
    """Statistical checks for the stochastic creation ops + dpsgd."""
    import paddle_tpu.fluid as fluid

    def run_op(op_type, attrs, inputs=None, outputs=("Out",)):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            block = main.global_block()
            feed = {}
            in_names = {}
            for slot, arr in (inputs or {}).items():
                n = f"in_{slot}"
                block.create_var(name=n, shape=arr.shape, dtype=arr.dtype)
                feed[n] = arr
                in_names[slot] = [n]
            for o in outputs:
                block.create_var(name=f"out_{o}")
            block.append_op(
                type=op_type, inputs=in_names,
                outputs={o: [f"out_{o}"] for o in outputs}, attrs=attrs,
            )
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.executor.Scope()):
            exe.run(startup)
            return [
                np.asarray(v)
                for v in exe.run(main, feed=feed, fetch_list=[f"out_{o}" for o in outputs])
            ]

    (u,) = run_op(
        "uniform_random",
        {"shape": [1000], "min": -2.0, "max": 2.0, "dtype": np.dtype("float32")},
    )
    assert u.min() >= -2.0 and u.max() <= 2.0
    assert abs(u.mean()) < 0.2

    (g,) = run_op(
        "gaussian_random",
        {"shape": [2000], "mean": 1.0, "std": 2.0, "dtype": np.dtype("float32")},
    )
    assert abs(g.mean() - 1.0) < 0.2 and abs(g.std() - 2.0) < 0.3

    (t,) = run_op(
        "truncated_gaussian_random",
        {"shape": [2000], "mean": 0.0, "std": 1.0, "dtype": np.dtype("float32")},
    )
    assert np.abs(t).max() <= 2.01 and abs(t.mean()) < 0.15

    rng = R(617)
    p = f32(rng.rand(200))
    gr = f32(rng.rand(200) * 0.1)
    (po,) = run_op(
        "dpsgd",
        {"clip": 1e6, "sigma": 0.0, "batch_size": 1.0},
        inputs={"Param": p, "Grad": gr, "LearningRate": f32([0.1])},
        outputs=("ParamOut",),
    )
    np.testing.assert_allclose(po, p - 0.1 * gr, rtol=1e-5, atol=1e-5)
