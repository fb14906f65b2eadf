"""Fault tolerance: hardened checkpoints, chaos injection, the elastic
mesh lifecycle, per-collective probes, and the train-loop recovery path.

The acceptance claims under test:

* a corrupt/truncated checkpoint is refused with an error NAMING the
  offending leaf (zip-CRC layer and our own checksum layer separately);
* ``MeshLifecycle.reshard`` after a simulated rank loss is bitwise-equal
  to a ``save_sharded``/``restore_sharded`` round trip on the shrunk
  mesh — the online elastic path IS the checkpoint path;
* generation 0 of a lifecycle builds the byte-identical mesh (and hence
  byte-identical HLO) of the fixed ``make_smoke_mesh`` it replaced;
* the watchdog blames a hung collective class, not slow compute, when a
  stall is injected into that class's probe window;
* the train CLI survives ``--chaos`` rank loss + checkpoint corruption
  end to end (subprocess), and SIGTERM lands a final verified
  checkpoint.
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from conftest import N_DEVICES
from test_gradsync import _toy_tree

from repro.checkpoint import ckpt
from repro.checkpoint.ckpt import CheckpointError
from repro.core import faultinject as FI
from repro.core import gradsync as GS
from repro.launch import mesh as LM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree(seed=0):
    rng = np.random.RandomState(seed)
    return {"a": {"w": rng.randn(64, 32).astype(np.float32)},
            "b": rng.randn(128).astype(np.float32),
            "scale": np.float32(rng.randn())}


# --------------------------------------------------------------------- #
# hardened checkpoint container
# --------------------------------------------------------------------- #

def test_ckpt_atomic_write_roundtrip_and_verify(tmp_path):
    path = str(tmp_path / "ck.npz")
    t = _tree()
    ckpt.save(path, t, step=7)
    # atomic rename left no temp debris
    assert [f for f in os.listdir(tmp_path) if ".tmp-" in f] == []
    got, step = ckpt.restore(path, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        t))
    assert step == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    info = ckpt.verify(path)
    assert info == {"step": 7, "leaves": 3, "checksummed": True}


def test_ckpt_truncated_raises_container_error(tmp_path):
    path = str(tmp_path / "ck.npz")
    ckpt.save(path, _tree())
    FI.corrupt_checkpoint(path, mode="truncate")
    with pytest.raises(CheckpointError,
                       match="unreadable .truncated or corrupt container"):
        ckpt.restore(path, _tree())
    with pytest.raises(CheckpointError):
        ckpt.verify(path)


def test_ckpt_bitflip_names_offending_leaf(tmp_path):
    path = str(tmp_path / "ck.npz")
    ckpt.save(path, _tree())
    FI.corrupt_checkpoint(path, leaf="params/a/w")
    with pytest.raises(CheckpointError,
                       match=r"leaf 'params/a/w' is corrupt"):
        ckpt.restore(path, _tree())
    with pytest.raises(CheckpointError, match=r"params/a/w"):
        ckpt.verify(path)
    # the untouched sibling leaf is still readable on its own
    data, meta = ckpt._open(path)
    np.testing.assert_array_equal(
        ckpt._read_leaf(data, meta, "params/b"), _tree()["b"])


def test_ckpt_checksum_layer_catches_valid_zip(tmp_path, monkeypatch):
    """A file whose zip container is intact but whose recorded checksums
    disagree (e.g. silent media corruption caught by neither layer below
    us) must fail OUR verification, naming the leaf."""
    path = str(tmp_path / "ck.npz")
    monkeypatch.setattr(ckpt, "_crc", lambda arr: 12345)
    ckpt.save(path, _tree())
    monkeypatch.undo()
    with pytest.raises(CheckpointError,
                       match=r"failed checksum verification "
                             r".recorded 0x00003039"):
        ckpt.restore(path, _tree())


def test_ckpt_legacy_without_checksums_still_restores(tmp_path):
    path = str(tmp_path / "ck.npz")
    legacy = str(tmp_path / "legacy.npz")
    ckpt.save(path, _tree(), step=3)
    data = np.load(path, allow_pickle=False)
    meta = json.loads(str(data["__meta__"]))
    del meta["checksums"]
    arrays = {k: data[k] for k in data.files if k != "__meta__"}
    with open(legacy, "wb") as fh:
        np.savez(fh, __meta__=json.dumps(meta), **arrays)
    got, step = ckpt.restore(legacy, _tree())
    assert step == 3
    np.testing.assert_array_equal(got["a"]["w"], _tree()["a"]["w"])
    assert ckpt.verify(legacy)["checksummed"] is False


def test_ckpt_missing_leaf_is_keyerror(tmp_path):
    path = str(tmp_path / "ck.npz")
    ckpt.save(path, {"a": np.zeros(4, np.float32)})
    with pytest.raises(KeyError, match="checkpoint missing leaf"):
        ckpt.restore(path, {"a": np.zeros(4, np.float32),
                            "extra": np.zeros(2, np.float32)})


# --------------------------------------------------------------------- #
# chaos spec parsing + deterministic injection
# --------------------------------------------------------------------- #

def test_chaos_parse_and_fire_once():
    inj = FI.parse_chaos("seed=3;rank_loss@5:n=2,via=ckpt;"
                         "ckpt_corrupt@4;timeout@7:class=z_ring,secs=0.5")
    assert inj.seed == 3
    assert [e.kind for e in inj.events] == ["ckpt_corrupt", "rank_loss",
                                            "timeout"]
    evs = inj.events_at(5)
    assert len(evs) == 1 and evs[0].get("n") == "2"
    assert inj.events_at(5) == []   # fires once, even on step retry
    assert inj.probe_delay(7, "z_ring") == 0.5
    assert inj.probe_delay(7, "xy_ar") == 0.0
    assert inj.step_stall(7) == 0.5
    assert inj.summary()["fired"] == 1


@pytest.mark.parametrize("bad", ["bogus@3", "rank_loss=5",
                                 "timeout@2:oops"])
def test_chaos_bad_tokens_raise(bad):
    with pytest.raises(ValueError, match="chaos token"):
        FI.parse_chaos(bad)


def test_chaos_corruption_is_deterministic(tmp_path):
    a, b, c = (str(tmp_path / f"{n}.npz") for n in "abc")
    for p in (a, b, c):
        ckpt.save(p, _tree())
    da = FI.corrupt_checkpoint(a, seed=0, step=4)
    db = FI.corrupt_checkpoint(b, seed=0, step=4)
    dc = FI.corrupt_checkpoint(c, seed=1, step=4)
    assert da == db
    assert open(a, "rb").read() == open(b, "rb").read()
    assert open(a, "rb").read() != open(c, "rb").read()


# --------------------------------------------------------------------- #
# mesh lifecycle
# --------------------------------------------------------------------- #

def test_lifecycle_gen0_is_byte_identical_to_smoke_mesh():
    """Swapping the fixed mesh factory for a lifecycle must change no
    HLO while the pool is intact (the chaos-off acceptance bar)."""
    shape = (2, 2, 2, 1) if N_DEVICES >= 8 else (1, 2, 2, 1)
    ref = LM.make_smoke_mesh(shape)
    life = LM.MeshLifecycle(*shape)
    mesh, axes = life.build()
    assert life.state == "active" and life.generation == 1
    assert [d.id for d in np.ravel(mesh.devices)] == \
        [d.id for d in np.ravel(ref.devices)]

    def prog(v):
        import repro.core.mesh as M
        return M.psum(v * 2.0, "x")
    x = np.ones((4, 4), np.float32)
    texts = [jax.jit(shard_map(prog, mesh=m, in_specs=(P("x", None),),
                               out_specs=P("x", None), check_vma=False)
                     ).lower(x).as_text() for m in (ref, mesh)]
    assert texts[0] == texts[1]


def test_lifecycle_failure_replan_and_recovery():
    # pin the pool to exactly 4 devices so one loss leaves a deficit
    life = LM.MeshLifecycle(2, 2, 1, 1, devices=jax.devices()[:4])
    life.build()
    lost = life.mark_failed(1)
    assert life.state == "degraded" and len(lost) == 1
    with pytest.raises(RuntimeError, match="needs 4 devices; only 3"):
        life.build()
    # largest g_data that fits 3 survivors with tensor=2 is 1
    assert life.replan()["g_data"] == 1
    assert life.replan(global_batch=8, overdecompose=2)["g_data"] == 1
    with pytest.raises(RuntimeError, match="no g_data"):
        life.replan(global_batch=7, overdecompose=2)
    # losing everything but one device cannot hold a 2-wide replica
    life.mark_failed(ids=[d.id for d in life.surviving[1:]])
    with pytest.raises(RuntimeError, match="cannot hold one model"):
        life.replan()
    life.mark_recovered()
    assert life.failed_ids == ()
    mesh, _ = life.build()
    assert mesh.devices.size == 4
    life.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        life.build()
    assert [e["event"] for e in life.log] == [
        "build", "mark_failed", "mark_failed", "mark_recovered", "build",
        "stop"]


def test_plan_fingerprint_invariant_across_gdata():
    """The bucket-plan fingerprint must ignore dp-dependent padding (so
    elastic restores across g_data pass) but catch real partitioning
    changes (bucket size)."""
    structs, specs = _toy_tree()
    from repro.optim import adamw as OPT
    # elastic re-shards only ever change g_data; the tensor factors (and
    # hence the per-leaf segment sizes) stay fixed
    shapes = ([(2, 2, 1, 1), (4, 2, 1, 1)] if N_DEVICES >= 8
              else [(2, 2, 1, 1), (1, 2, 1, 1)])
    fps = []
    for shape in shapes:
        axes = LM.bind_4d(LM.make_smoke_mesh(shape))
        plan = GS.make_plan(structs, specs, axes, 256,
                            no_decay=OPT._no_decay)
        fps.append(GS.plan_fingerprint(plan))
    assert fps[0] == fps[1]
    axes = LM.bind_4d(LM.make_smoke_mesh(shapes[0]))
    other = GS.make_plan(structs, specs, axes, 512,
                         no_decay=OPT._no_decay)
    assert GS.plan_fingerprint(other) != fps[0]


# --------------------------------------------------------------------- #
# online elastic re-shard == checkpoint restore (the tentpole claim)
# --------------------------------------------------------------------- #

def test_elastic_reshard_bitwise_equals_ckpt_restore(tmp_path):
    """Lose half the mesh mid-run; the state re-sharded online through
    ``MeshLifecycle.reshard`` must be bitwise-identical to restoring the
    checkpoint on the shrunk mesh, and training must continue with a
    finite loss."""
    from repro.configs import get_config
    from repro.core.gradsync import GradSyncConfig
    from repro.core.partition import spec_tree_to_pspecs
    from repro.launch import steps as ST
    from repro.optim import adamw as OPT

    shape = (2, 2, 2, 1) if N_DEVICES >= 8 else (2, 2, 1, 1)
    lose = shape[0] * shape[1] * shape[2] * shape[3] // 2
    B, S = 8, 32
    cfg = get_config("qwen3-1.7b").reduced()
    topts = ST.TrainOptions(overdecompose=2, dtype=jnp.float32,
                            gradsync=GradSyncConfig(zero=True,
                                                    bucket_mb=0.25))
    life = LM.MeshLifecycle(*shape)
    mesh, axes = life.build()
    params, specs = ST.init_model(cfg, axes, jax.random.PRNGKey(0),
                                  dtype=jnp.float32)
    params = ST.device_put_tree(mesh, params, spec_tree_to_pspecs(specs))
    tools = ST.make_gradsync_tools(cfg, mesh, axes, topts)
    state = tools.init(params)
    opt = OPT.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    step_fn, _, _ = ST.make_train_step(cfg, mesh, axes, opt, topts)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)),
                                   jnp.int32)}
    for _ in range(2):
        params, state, metrics = step_fn(params, state, batch)

    snap = ST.snapshot_state(params, state, tools, topts, step=1)
    path = str(tmp_path / "elastic.npz")
    ckpt.save_sharded(path, jax.tree.map(np.asarray,
                                         jax.device_get(params)),
                      state, tools.gather, step=1)

    life.mark_failed(lose)
    es = life.reshard(cfg, topts, snap, global_batch=B)
    assert life.generation == 2
    assert es.mesh.devices.size == int(np.prod(shape)) - lose
    assert es.axes.dp == shape[0] // 2

    # reference: the checkpoint path on the SAME shrunk mesh
    structs, _ = ST.init_model(cfg, es.axes, abstract=True,
                               dtype=jnp.float32)
    like_state = OPT.init_state(structs, abstract=True)
    p_ref, s_ref, stp = ckpt.restore_sharded(path, structs, like_state,
                                             es.tools.scatter)
    assert stp == 1
    for a, b in zip(jax.tree.leaves(es.params), jax.tree.leaves(p_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    full_on = jax.device_get(es.tools.gather(es.opt_state))
    full_ck = jax.device_get(es.tools.gather(s_ref))
    for a, b in zip(jax.tree.leaves(full_on), jax.tree.leaves(full_ck)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # a mismatched bucket-plan fingerprint must be refused loudly
    with pytest.raises(ValueError, match="bucket-plan fingerprint"):
        ST.restore_state(dict(snap, fingerprint="0123456789abcdef"),
                         cfg, es.mesh, es.axes, es.tools, topts)

    # training continues on the survivors
    step2, _, _ = ST.make_train_step(cfg, es.mesh, es.axes, opt, topts)
    _, _, m2 = step2(es.params, es.opt_state, batch)
    assert np.isfinite(float(m2["loss"]))


# --------------------------------------------------------------------- #
# per-collective probes + watchdog
# --------------------------------------------------------------------- #

def test_probes_monitor_and_merge(mesh4, axes4):
    from repro.core import calibrate as CB
    from repro.launch import probes as PRB
    pr = PRB.CollectiveProbes(mesh4, axes4)
    assert "xy_ar" in pr.classes          # x is 2-wide on every CI host
    for cls in pr.classes:
        assert pr.meta[cls]["p"] > 1
    pr.run(0)
    results = pr.run(1)
    for cls, r in results.items():
        assert r.measured_s > 0 and r.predicted_s > 0
        assert r.injected_s == 0.0
    recs = pr.records()
    assert {r["workload"] for r in recs} == \
        {f"collective:{c}" for c in pr.classes}
    prof = CB.CalibrationProfile(
        backend="cpu", n_devices=N_DEVICES, mesh_shape=(1, 2, 2, 1),
        alpha=4e-4, gamma=1e-3, link_bw=2e8, flops=2.4e11,
        overlap_efficiency=0.25)
    merged = pr.merge_into(prof)
    for cls in pr.classes:
        assert f"drift:collective:{cls}" in merged.probes


def test_watchdog_blames_hung_collective(mesh4, axes4):
    from repro.launch import probes as PRB
    cls = PRB.CollectiveProbes(mesh4, axes4).classes[0]
    inj = FI.parse_chaos(f"timeout@5:class={cls},secs=0.3")
    pr = PRB.CollectiveProbes(mesh4, axes4, injector=inj)
    wd = PRB.Watchdog(pr, factor=3.0, min_steps=3)
    for _ in range(4):
        wd.observe(0.1)
    assert not wd.stalled(0.12)
    assert wd.stalled(1.0)
    pr.run(3)
    pr.run(4)          # build the self-baseline history, injection-free
    v5 = wd.classify(5)
    assert v5["verdict"] == "hung_collective"
    # the injected class must be blamed; another class may be blamed
    # with it when its probe is slow on a loaded host (timing noise)
    assert cls in v5["suspects"]
    assert v5["results"][cls].injected_s == 0.3
    v6 = wd.classify(6)
    assert v6["verdict"] == "slow_compute" and v6["suspects"] == []


def test_watchdog_without_probes_defaults_to_compute():
    from repro.launch import probes as PRB
    wd = PRB.Watchdog(None, min_steps=2)
    assert not wd.stalled(99.0)       # no baseline yet
    wd.observe(0.1)
    wd.observe(0.1)
    assert wd.classify()["verdict"] == "slow_compute"


# --------------------------------------------------------------------- #
# train CLI end to end (subprocess)
# --------------------------------------------------------------------- #

def _train_cmd(tmp, *extra):
    return [sys.executable, "-m", "repro.launch.train",
            "--arch", "qwen3-1.7b", "--preset", "smoke",
            "--batch", "8", "--seq", "32", "--dp-bucket-mb", "0.25",
            "--zero", "--log-every", "1",
            "--telemetry", "--log-file", os.path.join(tmp, "t.jsonl"),
            *extra]


def _train_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


@pytest.mark.skipif(N_DEVICES < 8, reason="chaos smoke shrinks 8 -> 4")
def test_train_cli_chaos_rank_loss_recovers(tmp_path):
    """Corrupt the checkpoint, then drop half the ranks at the same step:
    the run must detect the corruption (naming the leaf), fall back to
    the in-memory snapshot, re-shard online, and finish with a finite
    loss and a contiguous step sequence."""
    tmp = str(tmp_path)
    ck = os.path.join(tmp, "ck.npz")
    cmd = _train_cmd(
        tmp, "--steps", "8", "--mesh", "2,2,2,1",
        "--ckpt", ck, "--ckpt-every", "2",
        "--chaos", "seed=0;ckpt_corrupt@5;rank_loss@5:n=4,via=ckpt")
    out = subprocess.run(cmd, cwd=ROOT, env=_train_env(),
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "chaos: ckpt_corrupt@5: flipped byte" in out.stdout
    assert "checkpoint unusable" in out.stdout
    assert "failed checksum verification" in out.stdout \
        or "is corrupt" in out.stdout
    assert "resharded: generation 2" in out.stdout

    losses = {}
    for line in out.stdout.splitlines():
        if line.startswith("step "):
            parts = line.split()
            losses[int(parts[1])] = float(parts[3])
    assert sorted(losses) == list(range(8))       # contiguous, no gap
    assert all(np.isfinite(v) for v in losses.values())
    # loss continuity across the recovery boundary (state resumed from
    # the step-4 snapshot, so step 5 continues the same trajectory)
    assert abs(losses[5] - losses[4]) < 0.5

    from repro.launch import telemetry as TL
    tfile = os.path.join(tmp, "t.jsonl")
    assert TL.validate_file(tfile) > 0
    events = [json.loads(l)["event"] for l in open(tfile)
              if '"kind": "event"' in l]
    for ev in ("ckpt_corrupt", "rank_loss", "ckpt_unusable", "resharded"):
        assert ev in events
    # the post-recovery final checkpoint verifies clean
    assert ckpt.verify(ck)["step"] == 7


def test_train_cli_sigterm_graceful_checkpoint(tmp_path):
    tmp = str(tmp_path)
    ck = os.path.join(tmp, "ck.npz")
    mesh = "2,2,2,1" if N_DEVICES >= 8 else "1,2,2,1"
    cmd = _train_cmd(tmp, "--steps", "5000", "--mesh", mesh,
                     "--ckpt", ck)
    proc = subprocess.Popen(cmd, cwd=ROOT, env=_train_env(),
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 420
        seen = 0
        for line in proc.stdout:
            if line.startswith("step ") and time.time() < deadline:
                seen += 1
                if seen >= 3:
                    break
        assert seen >= 3, "training never produced steps"
        proc.send_signal(signal.SIGTERM)
        rest = proc.stdout.read()
        rc = proc.wait(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == 0
    assert "caught SIGTERM: shutting down" in rest
    assert f"saved {ck}" in rest
    info = ckpt.verify(ck)
    assert info["checksummed"] and info["step"] >= 2
    events = [json.loads(l)["event"]
              for l in open(os.path.join(tmp, "t.jsonl"))
              if '"kind": "event"' in l]
    assert "shutdown" in events
