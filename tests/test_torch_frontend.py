"""Torch frontend tests (role of the reference's test/test_torch.py: 46
tests of allreduce/async/inplace, DistributedOptimizer, state broadcast,
compression).  Single-process here; two-process protocol in
tests/torch_worker.py via the launcher."""

import os
import sys

import numpy as np
import pytest

torch = pytest.importorskip("torch")

import horovod_tpu.torch as hvd_torch  # noqa: E402
from horovod_tpu.runner import launch  # noqa: E402
from horovod_tpu.runner.hosts import HostSpec  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestTorchOps:
    def test_allreduce_identity(self, hvd):
        # Sum is chip-weighted: one process speaks for local_size() chips.
        x = torch.randn(4, 3)
        out = hvd_torch.allreduce(x, op=hvd_torch.Sum)
        assert torch.allclose(out, hvd_torch.local_size() * x, atol=1e-5)

    def test_allreduce_average_default(self, hvd):
        x = torch.randn(5)
        out = hvd_torch.allreduce(x)
        assert torch.allclose(out, x, atol=1e-6)

    def test_allreduce_inplace(self, hvd):
        x = torch.randn(4)
        orig = x.clone()
        out = hvd_torch.allreduce_(x, op=hvd_torch.Sum)
        assert out is x
        assert torch.allclose(x, hvd_torch.local_size() * orig, atol=1e-5)

    def test_async_poll_synchronize(self, hvd):
        import time

        x = torch.randn(8)
        h = hvd_torch.allreduce_async(x, op=hvd_torch.Sum)
        deadline = time.time() + 10
        while not hvd_torch.poll(h):
            assert time.time() < deadline
            time.sleep(0.001)
        out = hvd_torch.synchronize(h)
        assert torch.allclose(out, hvd_torch.local_size() * x, atol=1e-5)

    def test_allgather(self, hvd):
        x = torch.randn(3, 2)
        out = hvd_torch.allgather(x)
        assert torch.allclose(out, x)

    def test_broadcast(self, hvd):
        x = torch.randn(4)
        out = hvd_torch.broadcast(x, 0)
        assert torch.allclose(out, x)

    def test_compression_fp16(self, hvd):
        """Reference test_compression_fp16 (test_torch.py:1171): values
        survive the fp16 round trip within half precision."""
        x = torch.randn(64)
        out = hvd_torch.allreduce(x, op=hvd_torch.Sum,
                                  compression=hvd_torch.Compression.fp16)
        assert out.dtype == torch.float32
        assert torch.allclose(out, hvd_torch.local_size() * x, atol=1e-1)

    def test_bfloat16_tensor(self, hvd):
        x = torch.randn(16).to(torch.bfloat16)
        out = hvd_torch.allreduce(x, op=hvd_torch.Sum)
        assert out.dtype == torch.bfloat16
        assert torch.allclose(out.float(),
                              hvd_torch.local_size() * x.float(), atol=1e-1)

    def test_int_tensor(self, hvd):
        x = torch.arange(6, dtype=torch.int32)
        out = hvd_torch.allreduce(x, op=hvd_torch.Sum)
        assert torch.equal(out, hvd_torch.local_size() * x)


class TestTorchAutograd:
    """The sync ops are autograd-differentiable (reference
    torch/mpi_ops.py:158-170 HorovodAllreduce/Allgather/Broadcast)."""

    def test_backward_through_allreduce(self, hvd):
        ls = hvd_torch.local_size()
        v = torch.tensor([1.0, 2.0], requires_grad=True)
        y = hvd_torch.allreduce(v * v, op=hvd_torch.Sum, name="tg.ar")
        y.sum().backward()
        # y = ls*v^2 (chip-weighted Sum); same-op backward is its VJP.
        assert torch.allclose(v.grad, ls * 2.0 * torch.tensor([1.0, 2.0]))

    def test_backward_through_allgather(self, hvd):
        v = torch.ones(2, 3, requires_grad=True)
        y = hvd_torch.allgather(v, name="tg.ag")
        (y * 3.0).sum().backward()
        # Process-level concat: FD-correct gradient, no chip factor.
        assert torch.allclose(v.grad, torch.full((2, 3), 3.0))

    def test_backward_through_broadcast(self, hvd):
        w = torch.tensor([5.0], requires_grad=True)
        z = hvd_torch.broadcast(w, 0, name="tg.bc")
        (z * 2.0).sum().backward()
        assert torch.allclose(w.grad, torch.tensor([2.0]))


class TestDistributedOptimizer:
    def _model(self):
        torch.manual_seed(0)
        return torch.nn.Sequential(
            torch.nn.Linear(4, 8), torch.nn.ReLU(), torch.nn.Linear(8, 1))

    def test_wraps_and_trains(self, hvd):
        model = self._model()
        opt = hvd_torch.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.05),
            named_parameters=model.named_parameters())
        x = torch.randn(32, 4)
        y = x.sum(dim=1, keepdim=True)
        losses = []
        for _ in range(30):
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(x), y)
            loss.backward()
            opt.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, losses[::10]

    def test_keeps_optimizer_class(self, hvd):
        model = self._model()
        opt = hvd_torch.DistributedOptimizer(
            torch.optim.Adam(model.parameters(), lr=1e-3),
            named_parameters=model.named_parameters())
        assert isinstance(opt, torch.optim.Adam)
        assert opt.param_groups[0]["lr"] == 1e-3

    def test_duplicate_names_rejected(self, hvd):
        model = self._model()
        with pytest.raises(ValueError, match="duplicate"):
            hvd_torch.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1),
                named_parameters=[("p", p) for p in model.parameters()])

    @pytest.mark.parametrize("op_name", ["Average", "Adasum"])
    def test_default_names_unique_across_group(self, hvd, op_name):
        """No named_parameters: every param (not every param GROUP) must
        get its own auto-name, for both wrapper classes — a model with 4
        params in one group used to collide on 'noname.0'."""
        model = self._model()
        opt = hvd_torch.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.05),
            op=getattr(hvd_torch, op_name))
        names = set(opt._parameter_names.values())
        assert len(names) == sum(1 for _ in model.parameters())
        x = torch.randn(8, 4)
        opt.zero_grad()
        torch.nn.functional.mse_loss(
            model(x), x.sum(dim=1, keepdim=True)).backward()
        opt.step()  # must not raise / deadlock

    def test_backward_passes_per_step(self, hvd):
        model = self._model()
        opt = hvd_torch.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.05),
            named_parameters=model.named_parameters(),
            backward_passes_per_step=2)
        x = torch.randn(8, 4)
        y = x.sum(dim=1, keepdim=True)
        # two backwards accumulate locally, then one reduced step
        loss = torch.nn.functional.mse_loss(model(x), y)
        loss.backward()
        loss = torch.nn.functional.mse_loss(model(x), y)
        loss.backward()
        opt.step()
        opt.zero_grad()

    def test_zero_grad_misuse_raises(self, hvd):
        model = self._model()
        opt = hvd_torch.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.05),
            named_parameters=model.named_parameters())
        loss = model(torch.randn(2, 4)).sum()
        loss.backward()
        with pytest.raises(AssertionError, match="zero_grad"):
            opt.zero_grad()
        opt.synchronize()  # drain

    def test_skip_synchronize(self, hvd):
        model = self._model()
        opt = hvd_torch.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.05),
            named_parameters=model.named_parameters())
        loss = model(torch.randn(2, 4)).sum()
        loss.backward()
        opt.synchronize()
        torch.nn.utils.clip_grad_norm_(model.parameters(), 1.0)
        with opt.skip_synchronize():
            opt.step()


class TestStateBroadcast:
    def test_broadcast_parameters(self, hvd):
        model = torch.nn.Linear(3, 2)
        before = {k: v.clone() for k, v in model.state_dict().items()}
        hvd_torch.broadcast_parameters(model.state_dict(), root_rank=0)
        for k, v in model.state_dict().items():
            assert torch.allclose(v, before[k])

    def test_broadcast_object(self, hvd):
        obj = {"lr": 0.1, "step": 7, "name": "adam"}
        out = hvd_torch.broadcast_object(obj, 0)
        assert out == obj

    def test_broadcast_optimizer_state(self, hvd):
        model = torch.nn.Linear(3, 2)
        opt = torch.optim.Adam(model.parameters(), lr=1e-3)
        model(torch.randn(4, 3)).sum().backward()
        opt.step()
        hvd_torch.broadcast_optimizer_state(opt, root_rank=0)
        # state survives the round trip
        st = opt.state_dict()["state"]
        assert all("exp_avg" in s for s in st.values())


@pytest.mark.slow
class TestTorchMultiProcess:
    def _spawn(self, tmp_path, scenario, nproc):
        import socket

        def free_port():
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        out = tmp_path / "out"
        env = {
            "PATH": os.environ.get("PATH", ""),
            "REPO": REPO,
            "JAX_PLATFORMS": "cpu",
            "HOROVOD_NUM_PROC": str(nproc),
            "HOROVOD_JAX_PORT": str(free_port()),
            "HOROVOD_NATIVE_PORT": str(free_port()),
        }
        args = [sys.executable,
                os.path.join(REPO, "tests", "torch_worker.py")]
        if scenario:
            args.append(scenario)
        rc = launch.launch_job(
            args,
            [HostSpec("localhost", 1)] * nproc,
            env=env,
            output_filename=str(out),
        )
        assert rc == 0, (out / "rank.0.stderr").read_text() + (
            out / f"rank.{nproc - 1}.stderr").read_text()
        for r in range(nproc):
            assert "TORCH-WORKER-OK" in (out / f"rank.{r}.stdout").read_text()

    def test_two_process_torch(self, tmp_path):
        self._spawn(tmp_path, None, 2)

    def test_adasum_delta_two_process(self, tmp_path):
        """Delta-model Adasum vs the pairwise oracle, 2 ranks (reference
        test_adasum_* parity)."""
        self._spawn(tmp_path, "adasum", 2)

    def test_adasum_delta_four_process(self, tmp_path):
        """Same at 4 ranks: two VHDD rounds exercise the recursion."""
        self._spawn(tmp_path, "adasum", 4)

    def test_adasum_delta_three_process(self, tmp_path):
        """Non-power-of-2 rank count: the eager Adasum falls back to
        gather + the serial pairwise oracle (the reference ERRORS here —
        adasum_mpi.cc:52-67; we degrade gracefully instead), and the
        delta optimizer must still match adasum_reduce_stack exactly."""
        self._spawn(tmp_path, "adasum", 3)
