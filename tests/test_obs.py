"""The program's own tracing (repro.obs): device scopes in the compiled
step's metadata, host spans in a profiler trace, and the compile counter.

Scopes are read from the optimized HLO's `op_name` metadata, which is what
a device trace is matched against."""
import glob
import importlib
import os
import re
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import reduced_config
from repro.models import build_model
from repro.train import (TrainConfig, TrainSupervisor, init_train_state,
                         make_train_step)
from repro.train.data import DataConfig, make_global_batch

SRC = Path(__file__).resolve().parents[1] / "src"
OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names(compiled_text: str) -> set:
    return set(OP_NAME.findall(compiled_text))


@pytest.fixture(scope="module")
def step_hlo():
    """The optimized HLO of a tiny Mamba2 step: bf16 compute, remat, a
    grad_reduce hook, and a sequence of whole SSD chunks."""
    cfg = reduced_config("mamba2-780m")
    model = build_model(cfg, remat=True)
    params, opt = init_train_state(model, jax.random.PRNGKey(0))
    step = make_train_step(
        model, TrainConfig(compute_dtype=jnp.bfloat16),
        grad_reduce=lambda t: jax.tree.map(lambda x: x * 0.5, t))
    seq = 2 * cfg.ssm_chunk
    batch = {"tokens": jnp.zeros((2, seq), jnp.int32)}
    return jax.jit(step).lower(params, opt, batch).compile().as_text()


def phases(names):
    """op_names by phase, by the rules bench/scopes.py applies."""
    out = {"forward": set(), "remat": set(), "backward": set(),
           "optimizer": set()}
    for n in names:
        if obs.ADAMW in n:
            out["optimizer"].add(n)
        elif "rematted_computation" in n:
            out["remat"].add(n)
        elif "transpose(" in n:
            out["backward"].add(n)
        elif obs.FORWARD in n:
            out["forward"].add(n)
    return out


def test_step_hlo_names_forward_remat_backward_and_adamw(step_hlo):
    names = op_names(step_hlo)
    split = phases(names)
    assert all(split.values()), {k: len(v) for k, v in split.items()}
    assert any(n.startswith("jit(train_step)/jvp(train.forward)/")
               for n in split["forward"])
    assert any("transpose(jvp(train.forward))" in n
               for n in split["backward"] | split["remat"])
    assert any(obs.GRAD_REDUCE in n for n in names)


def test_ssd_scope_in_forward_remat_and_backward(step_hlo):
    split = phases(op_names(step_hlo))
    for phase in ("forward", "remat", "backward"):
        assert any(obs.SSD in n for n in split[phase]), phase
    assert not any(obs.SSD in n for n in split["optimizer"])


@pytest.fixture(scope="module")
def kernel_step():
    """The tiny step's optimized HLO with the SSD forced onto the Pallas
    kernels (interpreted, as off the chip), and the SSD paths its trace
    counted."""
    import functools
    from repro.models import ssm
    cfg = reduced_config("mamba2-780m")
    model = build_model(cfg, remat=True)
    params, opt = init_train_state(model, jax.random.PRNGKey(0))
    step = make_train_step(model, TrainConfig(compute_dtype=jnp.bfloat16))
    batch = {"tokens": jnp.zeros((2, 2 * cfg.ssm_chunk), jnp.int32)}
    before = obs.ssd_paths()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ssm, "ssd_chunked", functools.partial(
            ssm.ssd_chunked, impl="interpret"))
        hlo = jax.jit(step).lower(params, opt, batch).compile().as_text()
    after = obs.ssd_paths()
    return hlo, {k: v - before.get(k, 0) for k, v in after.items()}


def test_ssd_kernel_scope_in_forward_remat_and_backward(kernel_step):
    hlo, _ = kernel_step
    split = phases(op_names(hlo))
    for phase in ("forward", "remat", "backward"):
        names = [n for n in split[phase] if obs.SSD_KERNEL in n]
        assert names, phase
        # nested in the SSD scope, so the SSD's reader counts it
        assert all(f"{obs.SSD}/{obs.SSD_KERNEL}" in n for n in names)


def test_ssd_path_counter_counts_the_traced_path(kernel_step, step_hlo):
    _, traced = kernel_step
    assert traced.get("kernel", 0) >= 1 and not traced.get("xla")
    # off the chip the automatic choice is the XLA path
    assert obs.ssd_paths().get("xla", 0) >= 1
    assert obs.SSD_KERNEL not in step_hlo


def test_tree_all_reduce_carries_comms_scopes():
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.comms import BucketedAllReduce, compile_program
        from repro.core.schedule import (compile_allgather,
                                         compile_reduce_scatter)
        from repro.topo import bidir_ring

        mesh = Mesh(np.array(jax.devices()), ('x',))
        topo = bidir_ring(4)
        red = BucketedAllReduce(
            rs_prog=compile_program(compile_reduce_scatter(topo,
                                                           num_chunks=2)),
            ag_prog=compile_program(compile_allgather(topo, num_chunks=2)),
            axis_name='x', bucket_bytes=1 << 20)
        f = jax.jit(jax.shard_map(red, mesh=mesh, in_specs=P(),
                                  out_specs=P(), check_vma=False))
        tree = {'a': jnp.ones((6, 5)), 'b': jnp.ones((7,))}
        out = f(tree)
        np.testing.assert_allclose(out['a'], 4 * np.ones((6, 5)))
        print(f.lower(tree).compile().as_text())
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    names = op_names(out.stdout)
    for scope in (obs.COMMS_GATHER, obs.COMMS_PERMUTE, obs.COMMS_SCATTER,
                  obs.COMMS_STAGE, obs.COMMS_BUCKET):
        assert any(scope in n for n in names), scope
    # every collective-permute is the comms.permute scope's
    permutes = re.findall(
        r'^\s*(?:ROOT )?%?[\w.-]+ = [^=]*? collective-permute(?:-start)?\('
        r'.*op_name="([^"]*)"',
        out.stdout, re.M)
    assert permutes and all(obs.COMMS_PERMUTE in n for n in permutes)


def _host_events(trace_dir) -> dict:
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    names = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                names[e.name] = names.get(e.name, 0) + 1
    return names


def test_supervisor_trace_holds_step_markers_and_spans(tmp_path):
    from repro.train import FaultInjector
    cfg = reduced_config("mamba2-780m")
    model = build_model(cfg, remat=True)
    params, opt = init_train_state(model, jax.random.PRNGKey(0))
    step_jit = jax.jit(make_train_step(model, TrainConfig()))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=2)
    mesh = jax.sharding.Mesh(jax.devices()[:1], ("data",))
    inj = FaultInjector.parse("3:0-1")
    hooked = []

    def step_fn(step, state):
        inj.check(step)
        p, o, m = step_jit(*state, make_global_batch(dc, step, mesh))
        return (p, o), m

    sup = TrainSupervisor(ckpt_dir=str(tmp_path), ckpt_every=3,
                          on_link_fault=hooked.append, trace_steps=(2, 2))
    _, final = sup.run(state=(params, opt), num_steps=5, step_fn=step_fn,
                       log=lambda s: None)
    assert final == 5 and len(hooked) == 1
    events = _host_events(tmp_path / "trace")
    # steps 2 and 3 traced, step 3 twice (the link fault retries it)
    assert events.get(obs.STEP) == 3
    assert events.get(obs.DISPATCH) == 3
    assert events.get(obs.WAIT) == 2
    assert events.get(obs.DATA_BATCH) == 2    # the fault precedes the batch
    assert events.get(obs.CHECKPOINT) == 1       # after step 2, as step 3
    assert events.get(obs.REPAIR) == 1


def test_supervisor_logs_the_ssd_paths_a_compile_traced(tmp_path):
    cfg = reduced_config("mamba2-780m")
    model = build_model(cfg, remat=True)
    params, opt = init_train_state(model, jax.random.PRNGKey(0))
    step_jit = jax.jit(make_train_step(model, TrainConfig()))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=2 * cfg.ssm_chunk,
                    global_batch=2)
    mesh = jax.sharding.Mesh(jax.devices()[:1], ("data",))

    def step_fn(step, state):
        p, o, m = step_jit(*state, make_global_batch(dc, step, mesh))
        return (p, o), m

    logs = []
    sup = TrainSupervisor(ckpt_dir=str(tmp_path), ckpt_every=100)
    sup.run(state=(params, opt), num_steps=3, step_fn=step_fn,
            log=logs.append, log_every=0)
    traced = [s for s in logs if "traced the SSD" in s]
    # once per compile (the first step's, and any again), on the XLA path
    # off the chip
    again = [s.split()[2] for s in logs if "compiled again" in s]
    assert [s.split()[2] for s in traced] == ["0"] + again, logs
    for s in traced:
        assert re.fullmatch(r"\[obs\] step \d+ traced the SSD scan: "
                            r"xla \d+", s), s


def test_compile_counter_counts_a_first_compile():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    before = obs.compile_totals()
    f(jnp.ones((3, 7))).block_until_ready()
    after = obs.compile_totals()
    for phase in ("trace", "lower", "compile"):
        assert after[phase].count > before[phase].count, phase
        assert after[phase].seconds > before[phase].seconds, phase
    assert obs.compile_seconds(after) > obs.compile_seconds(before)
    f(jnp.ones((3, 7))).block_until_ready()     # cached: nothing compiles
    assert obs.compile_totals() == after


def test_compile_counter_reads_up_to_a_moment():
    jax.jit(lambda x: jnp.cos(x) - 4.0)(jnp.ones(5)).block_until_ready()
    mark = time.time_ns()
    upto = obs.compile_totals(before_ns=mark)
    assert upto["compile"].count >= 1
    jax.jit(lambda x: x / 3.0 + 0.5)(jnp.ones(5)).block_until_ready()
    # what compiled after the moment is left out of the totals up to it
    assert obs.compile_totals(before_ns=mark) == upto
    assert obs.compile_totals()["compile"].count > upto["compile"].count


def test_compile_counter_survives_a_reload():
    importlib.reload(obs)
    x = jnp.ones(11)
    before = obs.compile_totals()
    jax.jit(lambda x: x * 5.0 - 2.0)(x).block_until_ready()
    after = obs.compile_totals()
    # one listener: one backend compile counted once
    assert after["compile"].count == before["compile"].count + 1


def test_supervisor_flags_a_step_that_compiles_again(tmp_path):
    f = jax.jit(lambda x: x * 2.0 + 1.0)

    def step_fn(step, state):
        # step 3 feeds a new shape: the step's program compiles again
        x = jnp.ones((5,) if step != 3 else (6,))
        return state + 1, {"loss": f(x).sum()}

    logs = []
    sup = TrainSupervisor(ckpt_dir=str(tmp_path), ckpt_every=100)
    sup.run(state=jnp.zeros(()), num_steps=5, step_fn=step_fn,
            log=logs.append, log_every=0)
    flagged = [s for s in logs if s.startswith("[obs]")]
    assert len(flagged) == 1, logs
    assert re.fullmatch(r"\[obs\] step 3 compiled again \(\d+\.\d{3} s\)",
                        flagged[0])


def test_launch_train_writes_the_asked_steps_trace(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "mamba2-780m",
         "--reduced", "--steps", "3", "--global-batch", "2", "--seq", "32",
         "--trace-steps", "1:2", "--ckpt-dir", str(tmp_path),
         "--ckpt-every", "100"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=str(SRC)))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done at step 3" in out.stdout
    events = _host_events(tmp_path / "trace")
    assert events.get(obs.STEP) == 2 and events.get(obs.DATA_BATCH) == 2
    bad = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--trace-steps", "4"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=str(SRC)))
    assert bad.returncode == 2 and "expected START:COUNT" in bad.stderr


def test_src_names_scopes_spans_and_events_only_through_obs():
    """One tracing system: every named_scope, TraceAnnotation and
    jax.monitoring use in src/repro takes its name from repro.obs."""
    uses = re.compile(r"(named_scope|TraceAnnotation)\(([^),]*)")
    names = {v for k, v in vars(obs).items() if k.isupper()
             and isinstance(v, str)}
    found = 0
    for path in (SRC / "repro").rglob("*.py"):
        text = path.read_text()
        for m in uses.finditer(text):
            found += 1
            arg = m.group(2).strip()
            assert arg.startswith("obs.") and getattr(
                obs, arg[4:]) in names, f"{path}: {m.group(0)}"
        if path.name != "obs.py":
            assert "jax.monitoring" not in text, path
    assert found >= 15


@pytest.fixture(scope="module")
def hybrid_step():
    """The optimized HLO of a tiny Zamba2 step (five layers, shared-block
    sites before layers 2 and 4): bf16 compute and remat; and the sites
    its trace counted."""
    cfg = reduced_config("zamba2-1.2b")
    model = build_model(cfg, remat=True)
    params, opt = init_train_state(model, jax.random.PRNGKey(0))
    step = make_train_step(model, TrainConfig(compute_dtype=jnp.bfloat16))
    batch = {"tokens": jnp.zeros((2, 2 * cfg.ssm_chunk), jnp.int32)}
    before = obs.shared_sites()
    hlo = jax.jit(step).lower(params, opt, batch).compile().as_text()
    after = obs.shared_sites()
    return hlo, {k: v - before.get(k, 0) for k, v in after.items()}


@pytest.mark.parametrize("phase", ["forward", "remat", "backward"])
def test_shared_block_scopes_in_each_pass(hybrid_step, phase):
    hlo, _ = hybrid_step
    names = phases(op_names(hlo))[phase]
    assert any(obs.SHARED in n for n in names)
    core = [n for n in names if obs.SHARED_ATTN in n]
    # the attention core nests in the block, so the block's reader counts
    # it; the block's projections and MLP lie outside the core
    assert core and all(f"{obs.SHARED}/{obs.SHARED_ATTN}" in n for n in core)
    assert any(obs.SHARED in n and obs.SHARED_ATTN not in n for n in names)
    assert not any(obs.SHARED in n for n in phases(op_names(hlo))[
        "optimizer"])


def test_shared_counter_counts_each_site_once_a_trace(hybrid_step):
    _, traced = hybrid_step
    assert traced == {0: 1, 1: 1}
