"""Training substrate: optimizer, microbatching, data determinism,
checkpointing, fault-tolerant supervision, elastic planning."""
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import build_model
from repro.train import (AdamWConfig, TrainConfig, TrainSupervisor,
                         checkpoint, elastic_plan, init_train_state,
                         make_train_step)
from repro.train.data import DataConfig, host_batch_slice
from repro.train.optimizer import global_norm, lr_schedule


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("qwen3-8b")
    model = build_model(cfg, remat=True)
    params, opt = init_train_state(model, jax.random.PRNGKey(0))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    return cfg, model, params, opt, dc


def batch_at(dc, step):
    return {k: jnp.asarray(v) for k, v in
            host_batch_slice(dc, step, 0, dc.global_batch).items()}


def test_loss_decreases(setup):
    cfg, model, params, opt, dc = setup
    step = jax.jit(make_train_step(model, TrainConfig(
        optimizer=AdamWConfig(lr=1e-3, warmup_steps=2))))
    first = last = None
    for i in range(20):
        params, opt, m = step(params, opt, batch_at(dc, 0))  # same batch
        if first is None:
            first = float(m["loss"])
        last = float(m["loss"])
    assert last < first - 0.5, (first, last)


def test_microbatch_equivalence(setup):
    """Grad accumulation must match the single-shot gradient."""
    cfg, model, params, opt, dc = setup
    tc1 = TrainConfig(microbatches=1)
    tc2 = TrainConfig(microbatches=2)
    from repro.train.train_step import loss_and_grad
    batch = batch_at(dc, 3)
    l1, g1, _ = loss_and_grad(model, params, batch, tc1)
    l2, g2, _ = loss_and_grad(model, params, batch, tc2)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-5)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-4)


def test_data_determinism_and_slicing():
    dc = DataConfig(vocab_size=1000, seq_len=16, global_batch=8)
    full = host_batch_slice(dc, 5, 0, 8)["tokens"]
    part = host_batch_slice(dc, 5, 3, 6)["tokens"]
    np.testing.assert_array_equal(full[3:6], part)
    again = host_batch_slice(dc, 5, 0, 8)["tokens"]
    np.testing.assert_array_equal(full, again)
    other = host_batch_slice(dc, 6, 0, 8)["tokens"]
    assert not np.array_equal(full, other)


def test_lr_schedule():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    assert float(lr_schedule(cfg, jnp.asarray(0))) < 0.2
    assert float(lr_schedule(cfg, jnp.asarray(10))) == pytest.approx(1.0)
    assert float(lr_schedule(cfg, jnp.asarray(99))) == pytest.approx(
        0.1, abs=0.02)


def test_checkpoint_roundtrip_and_gc(setup):
    cfg, model, params, opt, dc = setup
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3, 4):
            checkpoint.save(d, s, (params, opt))
        checkpoint.gc_old(d, keep=2)
        assert checkpoint.all_steps(d) == [3, 4]
        (p2, o2), step = checkpoint.restore(d, (params, opt))
        assert step == 4
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_save_async_writes_in_call_order(monkeypatch):
    """A slow earlier async save must not land after a later one: LATEST
    has to end on the newest step (the supervisor restores from it)."""
    write = checkpoint._write

    def slow_first(ckpt_dir, step, tree, host):
        if step == 3:
            time.sleep(0.3)
        return write(ckpt_dir, step, tree, host)

    monkeypatch.setattr(checkpoint, "_write", slow_first)
    with tempfile.TemporaryDirectory() as d:
        checkpoint.save_async(d, 3, {"n": jnp.zeros(())})
        checkpoint.save_async(d, 6, {"n": jnp.ones(())})
        checkpoint.wait_pending()
        assert checkpoint.latest_step(d) == 6
        state, step = checkpoint.restore(d, {"n": jnp.zeros(())})
        assert (step, float(state["n"])) == (6, 1.0)


def test_supervisor_recovers_from_crash(setup):
    cfg, model, params, opt, dc = setup
    step_fn_jit = jax.jit(make_train_step(model, TrainConfig()))
    crashed = {"n": 0}

    def step_fn(step, state):
        if step == 7 and crashed["n"] == 0:
            crashed["n"] += 1
            raise RuntimeError("injected node failure")
        p, o = state
        p, o, m = step_fn_jit(p, o, batch_at(dc, step))
        return (p, o), m

    with tempfile.TemporaryDirectory() as d:
        sup = TrainSupervisor(ckpt_dir=d, ckpt_every=3, max_restarts=2)
        state, final = sup.run(state=(params, opt), num_steps=10,
                               step_fn=step_fn, log=lambda s: None)
        assert final == 10
        assert crashed["n"] == 1


def test_elastic_plan():
    plan = elastic_plan(old_devices=256, new_devices=240, global_batch=256,
                        model_parallel=16)
    assert plan["mesh_shape"] == (15, 16)
    assert plan["microbatch_scale"] >= 1
    with pytest.raises(ValueError):
        elastic_plan(256, 250, 256, 16)   # 250 % 16 != 0


def test_global_norm():
    tree = {"a": jnp.ones((3,)), "b": jnp.full((4,), 2.0)}
    assert float(global_norm(tree)) == pytest.approx(np.sqrt(3 + 16))


def test_launch_pipeline_collectives(tmp_path):
    """End-to-end launch with --collectives pipeline: gradients cross
    devices through the BucketedAllReduce built from the cached
    `repro.allreduce` artifact (subprocess: forces 4 host devices)."""
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-8b",
         "--reduced", "--steps", "2", "--host-devices", "4",
         "--data-parallel", "4", "--collectives", "pipeline",
         "--schedule-cache", str(tmp_path / "cache"),
         "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "100"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=src))
    assert out.returncode == 0, f"stderr:\n{out.stderr[-2000:]}"
    assert "done at step 2" in out.stdout
    # the launch warmed the artifact cache (allreduce + per-axis pair)
    assert any((tmp_path / "cache").glob("allreduce-*.json")), \
        list((tmp_path / "cache").iterdir())


def test_supervisor_restore_resumes_exact_step():
    """Regression for the restore tuple-unpack bug: after a crash the
    supervisor must resume from the checkpoint's (state, step) — replaying
    the exact steps since the last save, not a mangled state tuple."""
    seen = []

    def step_fn(step, state):
        seen.append(step)
        if step == 7 and seen.count(7) == 1:
            raise RuntimeError("injected crash")
        return {"n": state["n"] + 1}, {}

    with tempfile.TemporaryDirectory() as d:
        sup = TrainSupervisor(ckpt_dir=d, ckpt_every=3, max_restarts=1)
        state, final = sup.run(state={"n": jnp.zeros(())}, num_steps=10,
                               step_fn=step_fn, log=lambda s: None)
    assert final == 10
    # crash at 7 restores the step-6 checkpoint and replays 6..9
    assert seen == [0, 1, 2, 3, 4, 5, 6, 7, 6, 7, 8, 9]
    assert int(state["n"]) == 10


@pytest.mark.parametrize("seed", range(25))
def test_elastic_plan_preserves_global_batch(seed):
    """Property: microbatch_scale is the MINIMAL positive integer making
    global_batch * scale divisible by the new data axis, so the summed
    gradient covers exactly the configured global batch."""
    rng = np.random.default_rng(seed)
    mp = int(rng.choice([1, 2, 4, 8, 16]))
    new_data = int(rng.integers(1, 64))
    gb = int(rng.integers(1, 512))
    plan = elastic_plan(old_devices=new_data * mp * 2,
                        new_devices=new_data * mp,
                        global_batch=gb, model_parallel=mp)
    scale = plan["microbatch_scale"]
    assert plan["mesh_shape"] == (new_data, mp)
    assert scale >= 1
    assert (gb * scale) % new_data == 0
    for s in range(1, scale):
        assert (gb * s) % new_data != 0


def test_straggler_monitor_converges_on_persistent_slowdown():
    """A sustained slowdown is flagged at first, then the EWMA walks up to
    the new speed and the flagging stops (the old behaviour dropped
    flagged samples, freezing the mean and flagging every step forever)."""
    from repro.train import StragglerMonitor
    m = StragglerMonitor()
    for i in range(10):
        assert not m.observe(i, 1.0)
    flags = [m.observe(10 + i, 5.0) for i in range(60)]
    assert flags[0]                       # the jump itself is a straggler
    assert not any(flags[-20:])           # ...but the monitor adapts
    assert m.ewma == pytest.approx(5.0, rel=0.05)
    assert len(m.flagged) < 15            # finitely many flags, not 60


def test_fault_injector_parse():
    from repro.train import FaultInjector
    inj = FaultInjector.parse("3:0-12")
    assert (inj.at_step, inj.u, inj.v) == (3, 0, 12)
    for bad in ("", "3", "0-1", "a:0-1", "3:01", "3:a-b"):
        with pytest.raises(ValueError):
            FaultInjector.parse(bad)


def test_supervisor_link_fault_retries_same_step_without_restore():
    from repro.train import FaultInjector, LinkFault
    inj = FaultInjector.parse("4:2-3")
    seen, hooked = [], []

    def step_fn(step, state):
        inj.check(step)
        seen.append(step)
        return {"n": state["n"] + 1}, {}

    with tempfile.TemporaryDirectory() as d:
        sup = TrainSupervisor(ckpt_dir=d, ckpt_every=100,
                              on_link_fault=hooked.append)
        state, final = sup.run(state={"n": jnp.zeros(())}, num_steps=8,
                               step_fn=step_fn, log=lambda s: None)
    assert final == 8
    # the faulted step is retried in place: no step skipped, none replayed
    assert seen == list(range(8))
    assert int(state["n"]) == 8
    assert len(hooked) == 1 and isinstance(hooked[0], LinkFault)
    assert (hooked[0].u, hooked[0].v) == (2, 3)
    assert hooked[0].transform_text == "@fail(2-3)"


def test_supervisor_link_fault_budget_and_no_hook():
    from repro.train import LinkFault

    def always_faulting(step, state):
        raise LinkFault(0, 1)

    with tempfile.TemporaryDirectory() as d:
        sup = TrainSupervisor(ckpt_dir=d, on_link_fault=lambda e: None,
                              max_link_faults=2)
        with pytest.raises(RuntimeError, match="exceeded 2 link faults"):
            sup.run(state={"n": jnp.zeros(())}, num_steps=4,
                    step_fn=always_faulting, log=lambda s: None)
        # without a repair hook a LinkFault is a real crash: it propagates
        # instead of burning the checkpoint-restart budget
        sup2 = TrainSupervisor(ckpt_dir=d)
        with pytest.raises(LinkFault):
            sup2.run(state={"n": jnp.zeros(())}, num_steps=4,
                     step_fn=always_faulting, log=lambda s: None)


def test_launch_train_survives_injected_link_fault(tmp_path):
    """End-to-end ISSUE acceptance: --inject-fault step:u-v on the pipeline
    collectives path.  The LinkFault reaches the supervisor, hot_swap
    repairs the data-axis schedules in place, the step is retried, and the
    run completes every step."""
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-8b",
         "--reduced", "--steps", "3", "--host-devices", "4",
         "--data-parallel", "4", "--collectives", "pipeline",
         "--inject-fault", "1:0-1",
         "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "100"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=src))
    assert out.returncode == 0, f"stderr:\n{out.stderr[-2000:]}"
    assert "[ft] link fault at step 1" in out.stdout
    assert "[repair] axis data" in out.stdout
    assert "done at step 3" in out.stdout
    assert "link faults repaired: True" in out.stdout
