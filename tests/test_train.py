"""Train library tests.

Pattern from the reference: train against small CPU worker groups
(python/ray/train/tests/test_data_parallel_trainer.py,
test_backend.py) — real actors, tiny models, checkpoint/restore and
failure-path assertions.
"""

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (
    Checkpoint,
    CheckpointConfig,
    DataParallelTrainer,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)


@pytest.fixture
def storage(tmp_path):
    return str(tmp_path / "results")


def test_single_worker_report(ray_start_4_cpus, storage):
    def loop(config):
        for i in range(3):
            train.report({"loss": 1.0 / (i + 1), "step": i})

    trainer = DataParallelTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t1", storage_path=storage),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 2
    assert result.metrics["training_iteration"] == 3


def test_context_ranks(ray_start_4_cpus, storage):
    def loop(config):
        ctx = train.get_context()
        train.report({"rank": ctx.get_world_rank(), "world": ctx.get_world_size()})

    trainer = DataParallelTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="t2", storage_path=storage),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["world"] == 2
    assert result.metrics["rank"] == 0  # controller surfaces rank-0 metrics


def test_checkpoint_roundtrip(ray_start_4_cpus, storage):
    def loop(config):
        ckpt = Checkpoint.from_state({"weights": [1.0, 2.0], "step": 7})
        train.report({"loss": 0.5}, checkpoint=ckpt)

    trainer = DataParallelTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t3", storage_path=storage),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.checkpoint is not None
    state = result.checkpoint.to_state()
    assert state["step"] == 7


def test_top_k_retention(ray_start_4_cpus, storage):
    def loop(config):
        for i in range(5):
            ckpt = Checkpoint.from_state({"i": i})
            train.report({"score": float(i % 3)}, checkpoint=ckpt)

    trainer = DataParallelTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="t4",
            storage_path=storage,
            checkpoint_config=CheckpointConfig(
                num_to_keep=2, checkpoint_score_attribute="score"
            ),
        ),
    )
    result = trainer.fit()
    assert result.error is None
    kept = sorted(os.listdir(os.path.join(storage, "t4")))
    assert len(kept) == 2
    # latest checkpoint must survive even if low-scoring
    assert "checkpoint_000004" in kept


def test_failure_restart_resumes_from_checkpoint(ray_start_4_cpus, storage):
    marker = os.path.join(storage, "poison")
    os.makedirs(storage, exist_ok=True)

    def loop(config):
        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            start = ckpt.to_state()["step"] + 1
        for i in range(start, 4):
            if i == 2 and not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                raise RuntimeError("boom at step 2")
            train.report(
                {"step": i, "resumed_from": start},
                checkpoint=Checkpoint.from_state({"step": i}),
            )

    trainer = DataParallelTrainer(
        loop,
        train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="t5",
            storage_path=storage,
            failure_config=FailureConfig(max_failures=2),
        ),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 3
    assert result.metrics["resumed_from"] == 2  # resumed, not restarted


def test_failure_exhausted(ray_start_4_cpus, storage):
    def loop(config):
        raise ValueError("always broken")

    trainer = DataParallelTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="t6",
            storage_path=storage,
            failure_config=FailureConfig(max_failures=1),
        ),
    )
    result = trainer.fit()
    assert result.error is not None
    assert "always broken" in str(result.error)


def test_jax_trainer_mesh_training(ray_start_4_cpus, storage):
    """End-to-end: JaxTrainer worker builds a mesh over the virtual CPU
    devices and runs a pjit data-parallel step (the §7.3 minimum slice)."""

    def loop(config):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.parallel import make_mesh

        mesh = make_mesh()  # all 8 virtual devices on the fsdp axis
        w = jnp.zeros((4,))
        xs = jnp.ones((8, 4))
        ys = jnp.full((8,), 3.0)

        @jax.jit
        def step(w, x, y):
            def loss(w):
                return jnp.mean((x @ w - y) ** 2)

            l, g = jax.value_and_grad(loss)(w)
            return w - 0.1 * g, l

        with jax.sharding.set_mesh(mesh):
            for i in range(10):
                w, l = step(w, xs, ys)
        train.report({"loss": float(l)})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t7", storage_path=storage),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["loss"] < 1.0


def test_dataset_shard_passthrough(ray_start_4_cpus, storage):
    class FakeDataset:
        def __init__(self, items):
            self.items = items

        def split(self, n):
            return [FakeDataset(self.items[i::n]) for i in range(n)]

    def loop(config):
        shard = train.get_dataset_shard("train")
        train.report({"n": len(shard.items)})

    trainer = DataParallelTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="t8", storage_path=storage),
        datasets={"train": FakeDataset(list(range(10)))},
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["n"] == 5


def test_elastic_resize_on_unschedulable_gang(ray_start_4_cpus, tmp_path):
    """Elastic training (reference: train/v2 ScalingPolicy): a gang that
    cannot be placed at full size restarts at a smaller size bounded by
    min_workers instead of failing."""
    from ray_tpu.train import RunConfig
    from ray_tpu.train.data_parallel_trainer import DataParallelTrainer
    from ray_tpu.air.config import FailureConfig, ScalingConfig

    def loop(config):
        from ray_tpu.train import session

        session.report({"world": session.get_context().get_world_size()})

    trainer = DataParallelTrainer(
        loop,
        # 8 x 1-CPU workers can never fit on 4 CPUs: must shrink 8->4
        scaling_config=ScalingConfig(
            num_workers=8,
            resources_per_worker={"CPU": 1},
            min_workers=2,
            placement_timeout_s=2.0,
        ),
        run_config=RunConfig(
            name="elastic", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=3),
        ),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["world"] == 4  # halved once: 8 -> 4 fits


def test_torch_trainer_ddp_gloo(ray_start_4_cpus):
    """TorchTrainer gang: gloo process group over framework rendezvous,
    DDP gradient averaging across 2 worker processes (reference:
    train/torch/config.py _TorchBackend + tests/test_backend.py)."""
    from ray_tpu import train
    from ray_tpu.air.config import ScalingConfig

    def loop(config):
        import numpy as np
        import torch
        import torch.distributed as dist

        from ray_tpu.train.torch_trainer import prepare_model

        ctx = train.get_context()
        assert dist.is_initialized()
        assert dist.get_world_size() == 2
        assert dist.get_rank() == ctx.get_world_rank()

        # allreduce sanity
        t = torch.tensor([float(ctx.get_world_rank() + 1)])
        dist.all_reduce(t)
        assert t.item() == 3.0  # 1 + 2

        # DDP: per-rank different data -> identical averaged grads
        torch.manual_seed(0)
        model = prepare_model(torch.nn.Linear(4, 1))
        x = torch.full((8, 4), float(ctx.get_world_rank()))
        loss = model(x).sum()
        loss.backward()
        g = model.module.weight.grad.numpy().copy()
        train.report({"grad0": float(g[0][0])})

    trainer = train.TorchTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2)
    )
    result = trainer.fit()
    # DDP averages grads: ranks saw x=0 and x=1 -> mean grad = 8*(0+1)/2
    assert abs(result.metrics["grad0"] - 4.0) < 1e-6
