"""Custom operator written against the NumPy-callback escape hatch
(ref: example/numpy-ops/custom_softmax.py — the classic CustomOp demo:
a softmax whose forward/backward run as host-side NumPy inside the
framework's dispatch).

TPU-native notes: the reference runs the callback on a dedicated worker
thread inside its engine (src/operator/custom/custom-inl.h); here the op
body executes through ``jax.pure_callback`` with a ``custom_vjp``, so it
still composes with autograd and jit (mxtpu/operator.py).

    python examples/numpy_ops/custom_softmax.py
"""
import numpy as np

import mxtpu as mx
from mxtpu import autograd


class Softmax(mx.operator.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        e = np.exp(x - x.max(axis=1, keepdims=True))
        self.assign(out_data[0], req[0], mx.nd.array(e / e.sum(axis=1,
                                                               keepdims=True)))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        y = out_data[0].asnumpy()
        gy = out_grad[0].asnumpy()
        gx = y * (gy - (gy * y).sum(axis=1, keepdims=True))
        self.assign(in_grad[0], req[0], mx.nd.array(gx))


@mx.operator.register("demo_softmax")
class SoftmaxProp(mx.operator.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=True)

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def create_operator(self, ctx, shapes, dtypes):
        return Softmax()


def main():
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.uniform(-2, 2, (4, 6)).astype(np.float32))
    x.attach_grad()
    with autograd.record():
        y = mx.nd.Custom(x, op_type="demo_softmax")
        loss = (y * y).sum()
    loss.backward()

    # check against the built-in softmax + its autograd
    x2 = mx.nd.array(x.asnumpy())
    x2.attach_grad()
    with autograd.record():
        y2 = mx.nd.softmax(x2, axis=1)
        loss2 = (y2 * y2).sum()
    loss2.backward()

    np.testing.assert_allclose(y.asnumpy(), y2.asnumpy(), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(x.grad.asnumpy(), x2.grad.asnumpy(),
                               rtol=1e-4, atol=1e-5)
    print("custom softmax forward+backward match the built-in: OK")
    return True


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
