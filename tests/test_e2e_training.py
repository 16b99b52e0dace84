"""End-to-end training tests — the driver-visible milestones
(SURVEY.md §7 phase 3 "MINIMUM E2E SLICE", BASELINE.json config 1) + the
eager-vs-jit parity assertion (§4.4 dy2static pattern)."""
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # distributed/parity suites: excluded from the fast gate

import paddle_tpu as paddle
from paddle_tpu import nn


def _toy_data(n=64, din=8, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, din).astype("float32")
    w_true = rng.randn(din, classes).astype("float32")
    y = (x @ w_true).argmax(-1).astype("int64")
    return x, y


class MLP(nn.Layer):
    def __init__(self, din=8, classes=4):
        super().__init__()
        self.fc1 = nn.Linear(din, 32)
        self.fc2 = nn.Linear(32, classes)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


class TestEagerTraining:
    def test_loss_decreases(self):
        x, y = _toy_data()
        net = MLP()
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        lossfn = nn.CrossEntropyLoss()
        losses = []
        for _ in range(30):
            out = net(paddle.to_tensor(x))
            loss = lossfn(out, paddle.to_tensor(y))
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5


class TestJitTraining:
    def test_train_step_loss_decreases(self):
        x, y = _toy_data()
        net = MLP()
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        step = paddle.jit.train_step(net, nn.CrossEntropyLoss(), opt)
        losses = [float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
                  for _ in range(30)]
        assert losses[-1] < losses[0] * 0.5

    def test_eager_jit_parity(self):
        """Same seed, same data => same loss curve eager vs jit
        (SURVEY.md §4.4 dy2static parity pattern)."""
        x, y = _toy_data()

        def run(jit):
            paddle.seed(123)
            net = MLP()
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters())
            lossfn = nn.CrossEntropyLoss()
            losses = []
            if jit:
                step = paddle.jit.train_step(net, lossfn, opt)
                for _ in range(10):
                    losses.append(float(step(paddle.to_tensor(x),
                                             paddle.to_tensor(y))))
            else:
                for _ in range(10):
                    out = net(paddle.to_tensor(x))
                    loss = lossfn(out, paddle.to_tensor(y))
                    loss.backward()
                    opt.step()
                    opt.clear_grad()
                    losses.append(float(loss))
            return losses

        eager = run(False)
        jit = run(True)
        np.testing.assert_allclose(eager, jit, rtol=2e-3, atol=1e-5)


class TestLeNetMNIST:
    def test_config1_lenet_mnist(self):
        """BASELINE.json config 1: LeNet on MNIST, loss decreases."""
        paddle.seed(42)
        net = paddle.vision.models.LeNet()
        ds = paddle.vision.datasets.MNIST(mode="train")
        loader = paddle.io.DataLoader(ds, batch_size=64, shuffle=True)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        step = paddle.jit.train_step(net, nn.CrossEntropyLoss(), opt)
        losses = []
        for i, (bx, by) in enumerate(loader):
            losses.append(float(step(bx, by)))
            if i >= 15:
                break
        assert np.mean(losses[-3:]) < losses[0] * 0.7

    def test_hapi_model_fit(self):
        """paddle.Model.fit over the same slice (SURVEY.md §2.2 HAPI)."""
        paddle.seed(7)
        net = MLP()
        model = paddle.Model(net)
        model.prepare(
            optimizer=paddle.optimizer.Adam(learning_rate=0.01,
                                            parameters=net.parameters()),
            loss=nn.CrossEntropyLoss(),
            metrics=paddle.metric.Accuracy(),
        )
        x, y = _toy_data(n=128)
        ds = paddle.io.TensorDataset([paddle.to_tensor(x),
                                      paddle.to_tensor(y)])
        model.fit(ds, batch_size=32, epochs=2, verbose=0)
        res = model.evaluate(ds, batch_size=32, verbose=0)
        assert res["loss"][0] < 1.2


class TestCheckpointResume:
    def test_save_load_resume(self, tmp_path):
        x, y = _toy_data()
        net = MLP()
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        lossfn = nn.CrossEntropyLoss()
        for _ in range(5):
            loss = lossfn(net(paddle.to_tensor(x)), paddle.to_tensor(y))
            loss.backward()
            opt.step()
            opt.clear_grad()
        p = str(tmp_path / "ckpt")
        paddle.save(net.state_dict(), p + ".pdparams")
        paddle.save(opt.state_dict(), p + ".pdopt")

        net2 = MLP()
        net2.set_state_dict(paddle.load(p + ".pdparams"))
        for (n1, p1), (n2, p2) in zip(net.named_parameters(),
                                      net2.named_parameters()):
            np.testing.assert_array_equal(p1.numpy(), p2.numpy())


class TestAMP:
    def test_auto_cast_changes_matmul_dtype(self):
        x = paddle.to_tensor(np.random.randn(4, 4).astype("float32"))
        w = paddle.to_tensor(np.random.randn(4, 4).astype("float32"))
        with paddle.amp.auto_cast(dtype="bfloat16"):
            out = paddle.matmul(x, w)
        assert out.dtype == paddle.bfloat16
        out2 = paddle.matmul(x, w)
        assert out2.dtype == paddle.float32

    def test_grad_scaler(self):
        net = MLP()
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters())
        scaler = paddle.amp.GradScaler(init_loss_scaling=128.0)
        x, y = _toy_data(n=16)
        loss = nn.CrossEntropyLoss()(net(paddle.to_tensor(x)),
                                     paddle.to_tensor(y))
        scaled = scaler.scale(loss)
        scaled.backward()
        scaler.step(opt)
        scaler.update()
        assert opt._step_count == 1
