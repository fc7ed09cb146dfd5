"""Train layer tests (reference model: python/ray/train/tests/test_backend.py,
test_data_parallel_trainer.py, test_new_persistence.py)."""

import os
import tempfile

import numpy as np
import pytest


def test_checkpoint_dict_roundtrip():
    from ray_tpu.train import Checkpoint
    ckpt = Checkpoint.from_dict({"step": 3, "w": np.arange(4)})
    data = ckpt.to_dict()
    assert data["step"] == 3
    np.testing.assert_array_equal(data["w"], np.arange(4))


def test_save_load_pytree_sharded(jax_cpu):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.train import load_pytree, save_pytree

    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("fsdp",))
    sh = NamedSharding(mesh, P("fsdp", None))
    tree = {
        "w": jax.device_put(jnp.arange(32.0).reshape(8, 4), sh),
        "b": jnp.ones(3),
        "meta": {"step": 7},
    }
    with tempfile.TemporaryDirectory() as d:
        save_pytree(tree, d)
        # load as numpy
        out = load_pytree(d)
        np.testing.assert_allclose(out["w"], np.arange(32.0).reshape(8, 4))
        np.testing.assert_allclose(out["b"], np.ones(3))
        assert out["meta"]["step"] == 7
        # load onto a different sharding (resharding on restore)
        sh2 = NamedSharding(mesh, P(None, "fsdp"))
        shardings = {"w": sh2, "b": NamedSharding(mesh, P()),
                     "meta": {"step": None}}
        out2 = load_pytree(d, shardings={"w": sh2,
                                         "b": NamedSharding(mesh, P()),
                                         "meta": {"step": None}})
        np.testing.assert_allclose(np.asarray(out2["w"]),
                                   np.arange(32.0).reshape(8, 4))


def test_jax_trainer_reports(ray_start):
    from ray_tpu.train import JaxTrainer, ScalingConfig, get_context, report

    def train_fn(config):
        ctx = get_context()
        for i in range(3):
            report({"round": i, "rank": ctx.get_world_rank(),
                    "world": ctx.get_world_size(),
                    "lr": config["lr"]})

    trainer = JaxTrainer(
        train_fn, train_loop_config={"lr": 0.1},
        scaling_config=ScalingConfig(num_workers=2))
    result = trainer.fit()
    assert result.error is None
    assert len(result.metrics_dataframe) == 3
    assert result.metrics["round"] == 2
    assert result.metrics["world"] == 2
    assert result.metrics["rank"] == 0
    assert result.metrics["lr"] == 0.1


def test_jax_trainer_checkpointing(ray_start, tmp_path):
    import ray_tpu.train as train
    from ray_tpu.train import (CheckpointConfig, Checkpoint, JaxTrainer,
                               RunConfig, ScalingConfig)

    def train_fn():
        ctx = train.get_context()
        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            start = ckpt.to_dict()["round"] + 1
        for i in range(start, 4):
            c = None
            if ctx.get_world_rank() == 0:
                c = Checkpoint.from_dict({"round": i})
            train.report({"round": i}, checkpoint=c)

    trainer = JaxTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            name="ckpt_test", storage_path=str(tmp_path),
            checkpoint_config=CheckpointConfig(num_to_keep=2)))
    result = trainer.fit()
    assert result.checkpoint is not None
    assert result.checkpoint.to_dict()["round"] == 3
    # resume from checkpoint: starts at round 4 => no rounds run
    trainer2 = JaxTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
        resume_from_checkpoint=result.checkpoint)
    r2 = trainer2.fit()
    assert r2.error is None
    assert r2.metrics_dataframe == []


def test_jax_trainer_failure_and_retry(ray_start, tmp_path):
    import ray_tpu.train as train
    from ray_tpu.train import (Checkpoint, FailureConfig, JaxTrainer,
                               RunConfig, ScalingConfig, TrainingFailedError)

    marker = str(tmp_path / "fail_once")

    def train_fn():
        ctx = train.get_context()
        ckpt = train.get_checkpoint()
        start = 0 if ckpt is None else ckpt.to_dict()["round"] + 1
        for i in range(start, 4):
            if i == 2 and not os.path.exists(marker):
                open(marker, "w").close()
                raise RuntimeError("boom at round 2")
            c = (Checkpoint.from_dict({"round": i})
                 if ctx.get_world_rank() == 0 else None)
            train.report({"round": i}, checkpoint=c)

    trainer = JaxTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="ft", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=1)))
    result = trainer.fit()
    assert result.error is None
    # resumed from round-1 checkpoint after the crash; all 4 rounds reported
    assert result.metrics["round"] == 3

    def always_fail():
        raise ValueError("nope")

    with pytest.raises(TrainingFailedError):
        JaxTrainer(always_fail,
                   scaling_config=ScalingConfig(num_workers=1)).fit()


def test_train_step_sharded_mlp(jax_cpu):
    """End-to-end: init + train a tiny MLP with fsdp strategy on the CPU
    mesh, loss decreases."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.train import init_train_state, make_train_step

    mesh = build_mesh(MeshConfig(data=2, fsdp=4))

    def init_fn():
        k = jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(k)
        return {"w1": jax.random.normal(k1, (8, 32)) * 0.1,
                "w2": jax.random.normal(k2, (32, 1)) * 0.1}

    def loss_fn(params, batch):
        h = jnp.tanh(batch["x"] @ params["w1"])
        pred = h @ params["w2"]
        return jnp.mean((pred - batch["y"]) ** 2)

    opt = optax.adam(1e-2)
    state = init_train_state(init_fn, opt, mesh, "fsdp")
    step = make_train_step(loss_fn, opt, mesh, "fsdp",
                           sample_params=state.params)
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True) > 0).astype(np.float32)
    batch = {"x": jnp.array(x), "y": jnp.array(y)}
    losses = []
    for _ in range(20):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    # the state init_train_state builds is typed like the state the step
    # returns: one trace, one compile
    assert step._cache_size() == 1


# Budget audit (PR 15, --durations): 62s — the multiprocess SPMD
# equivalence soak; single-process sharded training + torch DDP
# allreduce keep the fast-gate coverage.
@pytest.mark.slow
def test_multiprocess_gang_matches_single_process(ray_start, jax_cpu):
    """The REAL multi-host path (VERDICT r4 #2): two worker PROCESSES,
    each owning 4 virtual CPU devices, join one jax.distributed gang via
    BackendExecutor/JaxBackendConfig (coordinator on worker 0, gloo
    collectives) and run a dp x fsdp GPT train step over the 2-process
    8-device global mesh. The loss must match the single-process
    8-device baseline bit-for-bit.

    Reference analogue: python/ray/train/tests/test_backend.py +
    _internal/backend_executor.py:347 rank mapping."""
    from ray_tpu.parallel import mp_check
    from ray_tpu.train import ScalingConfig, report
    from ray_tpu.train.backend_executor import (BackendExecutor,
                                                JaxBackendConfig)

    baseline = mp_check.step_loss(2, 4)  # this process: 8 devices

    def train_fn():
        from ray_tpu.parallel import mp_check as mc
        from ray_tpu.train import report as rep
        loss = mc.step_loss(2, 4)  # global mesh spanning both processes
        rep({"loss": loss})

    ex = BackendExecutor(
        ScalingConfig(num_workers=2, resources_per_worker={"CPU": 0.5}),
        backend=JaxBackendConfig(distributed="force", platform="cpu",
                                 local_device_count=4))
    ex.start()
    try:
        infos = ex.worker_group.execute(
            lambda: __import__("jax").local_device_count(), timeout=240)
        assert infos == [4, 4], infos
        globals_ = ex.worker_group.execute(
            lambda: __import__("jax").device_count(), timeout=60)
        assert globals_ == [8, 8], globals_
        ex.start_training(train_fn, None)
        results = ex.get_next_results(timeout=420.0)
        assert results is not None
        losses = [r["metrics"]["loss"] for r in results]
        assert len(losses) == 2
        for x in losses:
            assert abs(x - baseline) < 1e-5, (x, baseline)
    finally:
        ex.shutdown()


def test_torch_trainer_ddp_allreduce(ray_start):
    """TorchTrainer forms a real gloo process group across the gang and
    DDP-averages gradients (reference: train/torch/torch_trainer.py)."""
    from ray_tpu.train import (ScalingConfig, TorchTrainer, get_context,
                               prepare_model, report)

    def train_fn():
        import torch
        import torch.distributed as dist
        ctx = get_context()
        rank = ctx.get_world_rank()
        assert dist.is_initialized()
        assert dist.get_world_size() == 2
        assert dist.get_rank() == rank

        torch.manual_seed(0)  # same init on both ranks
        model = prepare_model(torch.nn.Linear(4, 1))
        opt = torch.optim.SGD(model.parameters(), lr=0.1)
        # Different data per rank: DDP must average the gradients so the
        # ranks stay in lockstep.
        x = torch.full((8, 4), float(rank + 1))
        y = torch.zeros(8, 1)
        for _ in range(3):
            opt.zero_grad()
            loss = ((model(x) - y) ** 2).mean()
            loss.backward()
            opt.step()
        w = [p.detach().numpy().copy() for p in model.parameters()]
        # gather rank-0's weights to compare
        t = torch.cat([torch.as_tensor(a).flatten() for a in w])
        gathered = [torch.zeros_like(t) for _ in range(2)]
        dist.all_gather(gathered, t)
        in_sync = bool(torch.allclose(gathered[0], gathered[1]))
        report({"in_sync": in_sync, "loss": float(loss)})

    trainer = TorchTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=2))
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["in_sync"] is True
    assert result.metrics["loss"] < 100.0


# Budget audit (PR 15, --durations): 43s — third-party (HF) breadth
# integration, not core-path logic.
@pytest.mark.slow
def test_transformers_trainer_tiny_bert(ray_start, tmp_path):
    """HF Trainer runs on the gang with the gloo process group formed;
    metrics flow back through prepare_trainer's report bridge
    (reference: ray.train.huggingface.transformers). Offline: the tiny
    BERT is built from a config, never downloaded."""
    from ray_tpu.train import ScalingConfig, TransformersTrainer

    out_dir = str(tmp_path / "hf")

    def train_fn(config):
        import numpy as np
        import torch
        from torch.utils.data import Dataset as TorchDataset
        from transformers import (BertConfig,
                                  BertForSequenceClassification,
                                  Trainer, TrainingArguments)

        from ray_tpu.train import prepare_trainer

        class Synth(TorchDataset):
            def __len__(self):
                return 64

            def __getitem__(self, i):
                rng = np.random.RandomState(i)
                ids = torch.tensor(rng.randint(0, 64, size=16))
                return {"input_ids": ids,
                        "attention_mask": torch.ones(16, dtype=torch.long),
                        "labels": torch.tensor(int(i % 2))}

        model = BertForSequenceClassification(BertConfig(
            vocab_size=64, hidden_size=16, num_hidden_layers=1,
            num_attention_heads=2, intermediate_size=32,
            max_position_embeddings=32))
        args = TrainingArguments(
            output_dir=config["out"], num_train_epochs=1,
            per_device_train_batch_size=8, logging_steps=2,
            report_to=[], save_strategy="no", use_cpu=True,
            disable_tqdm=True)
        trainer = Trainer(model=model, args=args, train_dataset=Synth())
        trainer = prepare_trainer(trainer)
        # torchrun-style env must have engaged HF's distributed path
        assert args.world_size == 2, args.world_size
        trainer.train()

    result = TransformersTrainer(
        train_fn, train_loop_config={"out": out_dir},
        scaling_config=ScalingConfig(num_workers=2)).fit()
    assert result.error is None, result.error
    assert result.metrics_dataframe, "no metrics reported"
    assert any("loss" in row for row in result.metrics_dataframe)


def test_build_tf_config_pure():
    """TF_CONFIG cluster-spec assembly (reference:
    train/tensorflow/config.py _setup_tensorflow_environment)."""
    import json

    from ray_tpu.train import build_tf_config

    cfg = json.loads(build_tf_config([("10.0.0.1", 1111),
                                      ("10.0.0.2", 2222)], rank=1))
    assert cfg["cluster"]["worker"] == ["10.0.0.1:1111", "10.0.0.2:2222"]
    assert cfg["task"] == {"type": "worker", "index": 1}
    with pytest.raises(ValueError):
        build_tf_config([("a", 1)], rank=3)


def test_tensorflow_backend_exports_tf_config(ray_start):
    """The TF backend must export a coherent TF_CONFIG on every gang
    member (tensorflow itself is not needed: MultiWorkerMirroredStrategy
    reads this env in the user loop)."""
    import json

    from ray_tpu.train import (ScalingConfig, TensorflowTrainer,
                               get_context, report)

    def train_fn():
        import os
        cfg = json.loads(os.environ["TF_CONFIG"])
        # Coherence asserted in-loop: failures propagate through fit().
        assert cfg["task"]["type"] == "worker"
        assert cfg["task"]["index"] == get_context().get_world_rank()
        assert len(set(cfg["cluster"]["worker"])) == 2
        report({"workers": cfg["cluster"]["worker"]})

    trainer = TensorflowTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=2,
                                     resources_per_worker={"CPU": 1}))
    result = trainer.fit()
    assert result.error is None
    assert len(result.metrics["workers"]) == 2
