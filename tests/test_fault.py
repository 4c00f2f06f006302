"""Watchdog, int8-psum collective, and the kill/resume pipeline drill."""
import os
import signal
import subprocess
import sys
import textwrap
import time

from repro.train.fault import Watchdog, WatchdogConfig, latest_restart_point


def test_watchdog_states(tmp_path):
    hb = tmp_path / "HEARTBEAT"
    wd = Watchdog(str(hb), WatchdogConfig(stale_after_s=10))
    assert wd.check() == "missing"
    hb.write_text(f"5 {time.time()}")
    assert wd.check() == "ok"
    assert not wd.should_restart()
    # stale heartbeat
    hb.write_text(f"6 {time.time() - 100}")
    assert wd.check() == "stale"
    assert wd.should_restart()
    # regression (restarted host reports older step while we expect newer)
    hb.write_text(f"2 {time.time()}")
    wd2 = Watchdog(str(hb), WatchdogConfig(stale_after_s=1000))
    wd2.last_step = 6
    assert wd2.check() == "regressed"


def test_watchdog_config_not_shared(tmp_path):
    """Regression: the default WatchdogConfig must be per-instance — a
    dataclass default instance shared across watchdogs would let one
    watchdog's threshold tweak leak into every other."""
    a = Watchdog(str(tmp_path / "a"))
    b = Watchdog(str(tmp_path / "b"))
    assert a.cfg is not b.cfg
    a.cfg.stale_after_s = 1.0
    assert b.cfg.stale_after_s == WatchdogConfig().stale_after_s


def test_latest_restart_point(tmp_path):
    import jax.numpy as jnp

    from repro import checkpoint as ckpt

    assert latest_restart_point(str(tmp_path / "nope")) is None
    ckpt.save(str(tmp_path), 7, {"x": jnp.ones(2)})
    os.makedirs(tmp_path / "step_00000009.tmp")  # crashed write
    assert latest_restart_point(str(tmp_path)) == 7


INT8_PSUM_SCRIPT = textwrap.dedent("""
    import os
    # pin CPU BEFORE jax imports: with libtpu in the image an unset
    # JAX_PLATFORMS makes jax probe the TPU metadata server for minutes
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.optim.compression import int8_psum

    mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
    x = jnp.asarray(np.random.RandomState(0).randn(4, 1000), jnp.float32)

    f = jax.shard_map(lambda a: int8_psum(a[0], "pod"), mesh=mesh,
                      in_specs=P("pod"), out_specs=P(), check_vma=False)
    with mesh:
        got = f(x)
    want = np.sum(np.asarray(x), axis=0)
    err = np.abs(np.asarray(got) - want)
    # blockwise int8: error bounded by sum of per-shard quant steps
    bound = 4 * np.abs(x).max() / 127 + 1e-5
    assert err.max() <= bound, (err.max(), bound)
    print("INT8_PSUM_OK", err.max())
""")


def test_int8_psum_shard_map():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", INT8_PSUM_SCRIPT],
                         cwd="/root/repo", env=env, capture_output=True,
                         text=True, timeout=300)
    assert "INT8_PSUM_OK" in out.stdout, out.stdout + out.stderr[-2000:]


# ------------------------------------------- async refresh plane (§12)


def test_restore_discards_inflight_pending(tmp_path):
    """A checkpoint written mid-interval (refresh dispatched, swap not
    yet due) excludes the pending payloads; the restore keeps the
    init-state zeros for them and discard_inflight clears pending_at —
    so the resumed run never swaps in a buffer it did not dispatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import checkpoint as ckpt
    from repro.config import OptimizerConfig, PrismConfig
    from repro.optim import base, make_optimizer
    from repro.train.fault import discard_inflight

    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (48, 32))}
    axes = {"w": ("embed", "mlp")}
    cfg = OptimizerConfig(name="muon", learning_rate=0.05,
                          precond_every=4, precond_async=True,
                          precond_swap_delay=2,
                          prism=PrismConfig(degree=2, iterations=3,
                                            warm_alpha_iters=1,
                                            sketch_dim=8))
    opt = make_optimizer(cfg, axes)
    p, s = params, opt.init(params)
    # two steps, then a refresh dispatched at t=2 whose swap is due at
    # t=4 — checkpoint lands mid-interval at t=3
    for t in range(3):
        if t == 2:
            s = base.install_pending(s, opt.refresh(s, key), at_step=2)
        g = jax.tree.map(lambda q: 0.1 * jnp.ones_like(q), params)
        p, s = opt.update(g, s, p, t, jax.random.fold_in(key, t),
                          refresh=False)
    assert int(s["pending_at"]) == 2
    ckpt.save(str(tmp_path), 3, {"opt": s}, drop=base.PENDING_STATE_KEYS)
    # on-disk file really excludes every pending payload
    data = np.load(tmp_path / "step_00000003" / "tree.npz")
    assert not any(base.PENDING_STATE_KEYS.intersection(k.split("|"))
                   for k in data.files)
    # restore into a fresh init target; pending keys fall back to zeros
    target = {"opt": opt.init(params)}
    _, restored = ckpt.restore(str(tmp_path), target,
                               allow_missing=base.PENDING_STATE_KEYS)
    rs = discard_inflight(restored["opt"])
    assert int(rs["pending_at"]) == base.NO_PENDING
    slot = base._flat_slots(rs["leaves"])[0][0]
    np.testing.assert_array_equal(np.asarray(slot["ortho_p"], np.float32),
                                  0.0)
    # non-pending state round-trips exactly (mom, active cache, count)
    orig = base._flat_slots(s["leaves"])[0][0]
    np.testing.assert_array_equal(np.asarray(slot["mom"]),
                                  np.asarray(orig["mom"]))
    np.testing.assert_array_equal(np.asarray(slot["ortho"]),
                                  np.asarray(orig["ortho"]))
    # the resumed run never consumes the zeroed pending buffer: the swap
    # cond stays untaken on the very step the old swap was due
    p2, s2 = opt.update(jax.tree.map(lambda q: 0.1 * jnp.ones_like(q),
                                     params),
                        rs, params, 4, jax.random.fold_in(key, 4),
                        refresh=False)
    assert int(s2["pending_at"]) == base.NO_PENDING
    slot2 = base._flat_slots(s2["leaves"])[0][0]
    np.testing.assert_array_equal(np.asarray(slot2["ortho"]),
                                  np.asarray(orig["ortho"]))


# --------------------------------------- kill/resume pipeline drill (§13)


def _drill_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["REPRO_KERNEL_MODE"] = "ref"
    env["PYTHONPATH"] = "src"
    return env


def _drill_cmd(ckpt_dir, steps, extra=()):
    return [sys.executable, "-m", "repro.train.fault",
            "--ckpt_dir", str(ckpt_dir), "--steps", str(steps),
            "--ckpt_every", "2", *extra]


def _losses(stdout):
    """{step: hex_loss} from DRILL_LOSS lines."""
    out = {}
    for line in stdout.splitlines():
        if line.startswith("DRILL_LOSS "):
            _, t, h = line.split()
            out[int(t)] = h
    return out


def _kill_after_checkpoint(ckpt_dir, proc, min_step=2, timeout_s=420):
    """Poll the drill's heartbeat until a complete checkpoint >= min_step
    exists AND the run has moved past it, then SIGKILL mid-step."""
    wd = Watchdog(os.path.join(str(ckpt_dir), "HEARTBEAT"))
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        assert proc.poll() is None, \
            "drill exited before the kill: " + proc.stdout.read()
        hb = wd.read()
        if hb is not None and hb[0] >= min_step and \
                (latest_restart_point(str(ckpt_dir)) or 0) >= min_step:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
            return
        time.sleep(0.2)
    proc.kill()
    raise AssertionError("drill never reached a killable checkpoint: "
                         + proc.stdout.read())


def test_kill_resume_bitwise(tmp_path):
    """The tentpole drill: a pipeline training subprocess on the 8-device
    (pod=2, data=2, model=2) host mesh is SIGKILLed mid-run; the relaunch
    resumes from the newest complete checkpoint and its per-step losses
    continue BITWISE (hex-compared) against an uninterrupted run — sync
    preconditioners make resume exactly deterministic."""
    steps = 5
    ref_dir, kill_dir = tmp_path / "ref", tmp_path / "kill"
    ref = subprocess.run(_drill_cmd(ref_dir, steps), env=_drill_env(),
                         cwd="/root/repo", capture_output=True, text=True,
                         timeout=560)
    ref_losses = _losses(ref.stdout)
    assert sorted(ref_losses) == list(range(steps)), \
        ref.stdout + ref.stderr[-4000:]

    proc = subprocess.Popen(_drill_cmd(kill_dir, steps), env=_drill_env(),
                            cwd="/root/repo", stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    _kill_after_checkpoint(kill_dir, proc)
    pre = _losses(proc.stdout.read())
    # the pre-kill prefix already matches the reference bitwise
    for t, h in pre.items():
        assert h == ref_losses[t], (t, h, ref_losses[t])

    resumed = subprocess.run(_drill_cmd(kill_dir, steps),
                             env=_drill_env(), cwd="/root/repo",
                             capture_output=True, text=True, timeout=560)
    assert "resumed from step" in resumed.stdout, \
        resumed.stdout + resumed.stderr[-4000:]
    post = _losses(resumed.stdout)
    assert post, resumed.stdout
    assert min(post) >= 2  # restarted from a checkpoint, not from scratch
    for t, h in post.items():
        assert h == ref_losses[t], (t, h, ref_losses[t])
    assert max(post) == steps - 1
    # per-stage heartbeats carry the same Watchdog-parseable contract
    for s in range(2):
        hb = Watchdog(str(kill_dir / f"HEARTBEAT.stage{s}")).read()
        assert hb is not None and hb[0] == steps - 1, (s, hb)


def test_kill_resume_async_staleness_reset(tmp_path):
    """Async-precond variant: resume is NOT bitwise (the documented
    staleness reset, DESIGN.md §12/§13) — instead the relaunch must
    resume from a checkpoint, re-bootstrap the refresh plane via
    discard_inflight, and finish with finite losses."""
    import json
    import math

    steps = 4
    d = tmp_path / "drill"
    proc = subprocess.Popen(
        _drill_cmd(d, steps, extra=("--async_precond",)),
        env=_drill_env(), cwd="/root/repo", stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    _kill_after_checkpoint(d, proc)
    resumed = subprocess.run(
        _drill_cmd(d, steps, extra=("--async_precond",)),
        env=_drill_env(), cwd="/root/repo", capture_output=True,
        text=True, timeout=560)
    assert "resumed from step" in resumed.stdout, \
        resumed.stdout + resumed.stderr[-4000:]
    post = _losses(resumed.stdout)
    assert post and max(post) == steps - 1, resumed.stdout
    assert all(math.isfinite(float.fromhex(h)) for h in post.values())
    done = [line for line in resumed.stdout.splitlines()
            if line.startswith("DRILL_DONE ")]
    telemetry = json.loads(done[0][len("DRILL_DONE "):])
    # the resumed service re-bootstrapped (never consumed stale pendings)
    assert telemetry["bootstrap"] >= 1, telemetry
