"""Structured error layer (VERDICT r2 #10; reference enforce.h):
negative paths assert error CLASS + structured PAYLOAD, not message
strings."""
from __future__ import annotations

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import enforce as errors


def _t(a):
    return paddle.to_tensor(np.asarray(a))


class TestErrorHierarchy:
    def test_typed_errors_subclass_builtins(self):
        # the reference's pybind mapping: typed error AND builtin
        assert issubclass(errors.InvalidArgumentError, ValueError)
        assert issubclass(errors.OutOfRangeError, IndexError)
        assert issubclass(errors.NotFoundError, KeyError)
        assert issubclass(errors.UnimplementedError, NotImplementedError)
        assert issubclass(errors.ExecutionTimeoutError, TimeoutError)
        for cls in errors.BUILTIN_TO_TYPED.values():
            assert issubclass(cls, errors.EnforceNotMet)
            assert issubclass(cls, RuntimeError)

    def test_enforce_payload(self):
        with pytest.raises(errors.InvalidArgumentError) as e:
            errors.enforce(False, "bad dim", hint="check shapes",
                           axis=2, rank=1)
        err = e.value
        assert err.code == "INVALID_ARGUMENT"
        assert err.hint == "check shapes"
        assert err.context == {"axis": 2, "rank": 1}
        assert "Error Message Summary" in str(err)

    def test_enforce_eq_and_shape_match(self):
        with pytest.raises(errors.InvalidArgumentError) as e:
            errors.enforce_eq(3, 4, what="degree")
        assert e.value.context["lhs"] == 3 and e.value.context["rhs"] == 4
        with pytest.raises(errors.InvalidArgumentError) as e:
            errors.enforce_shape_match((2, 3), (2, 4), what="weight")
        assert e.value.context["got_shape"] == (2, 3)
        assert e.value.context["expected_shape"] == (2, 4)
        errors.enforce_shape_match((2, 3), (-1, 3))  # wildcard ok


class TestDispatchEnrichment:
    def test_op_error_carries_op_and_shapes(self):
        with pytest.raises(errors.InvalidArgumentError) as e:
            paddle.concat([_t(np.zeros((2, 3), np.float32)),
                           _t(np.zeros((2, 4), np.float32))], axis=0)
        err = e.value
        assert err.op == "concat"
        assert (2, 3) in err.context["input_shapes"]
        assert (2, 4) in err.context["input_shapes"]

    def test_builtin_except_still_catches(self):
        # wrapping must never break `except ValueError` callers
        with pytest.raises(ValueError):
            paddle.concat([_t(np.zeros((2, 3), np.float32)),
                           _t(np.zeros((2, 4), np.float32))], axis=0)

    def test_enforce_not_met_gets_op_attached(self):
        with pytest.raises(errors.InvalidArgumentError) as e:
            paddle.vision.ops.roi_align(
                _t(np.zeros((2, 1, 4, 4), np.float32)),
                _t(np.zeros((2, 4), np.float32)),
                _t(np.array([1, 0], np.int32)), 2)
        assert e.value.op == "roi_align"

    def test_grad_path_enriches_too(self):
        x = _t(np.zeros((2, 3), np.float32))
        x.stop_gradient = False
        y = _t(np.zeros((2, 4), np.float32))
        y.stop_gradient = False
        with pytest.raises(errors.InvalidArgumentError) as e:
            paddle.concat([x, y], axis=0)
        assert e.value.op == "concat"


class TestNativeBoundary:
    def test_native_status_maps_to_typed(self):
        from paddle_tpu.distributed.ps import PsClient, PsServer

        srv = PsServer()
        try:
            with PsClient(port=srv.port) as cli:
                # pull from a table that does not exist: native -1
                with pytest.raises(errors.NotFoundError) as e:
                    cli.pull_sparse(99, [1], dim=4)
                assert e.value.context["status"] == -1
                # dim mismatch: native -4 -> InvalidArgument
                cli.create_sparse_table(0, 4, optimizer="sgd")
                with pytest.raises(errors.InvalidArgumentError) as e:
                    cli.pull_sparse(0, [1], dim=8)
                assert e.value.context["status"] == -4
        finally:
            srv.stop()


class TestVerbosityFlag:
    def test_call_stack_level_gates_context(self):
        err = errors.InvalidArgumentError("boom", op="matmul",
                                          got_shape=(2, 3))
        old = paddle.get_flags("FLAGS_call_stack_level")
        try:
            paddle.set_flags({"FLAGS_call_stack_level": 0})
            assert "got_shape" not in str(err)
            paddle.set_flags({"FLAGS_call_stack_level": 1})
            assert "got_shape" in str(err)
            assert "[Operator: matmul]" in str(err)
        finally:
            paddle.set_flags(old)

    def test_level2_includes_cause(self):
        old = paddle.get_flags("FLAGS_call_stack_level")
        try:
            paddle.set_flags({"FLAGS_call_stack_level": 2})
            try:
                try:
                    raise ValueError("inner boom")
                except ValueError as inner:
                    raise errors.InvalidArgumentError("outer") from inner
            except errors.InvalidArgumentError as err:
                assert "inner boom" in str(err)
        finally:
            paddle.set_flags(old)
