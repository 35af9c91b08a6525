"""Bring-up contracts (ISSUE 21): a server that lets go of its device when
told to stop, a --platform flag that wins, ONE compile-cache placement rule,
device errors that propagate instead of hiding behind a host fallback, and a
chip_smoke.py that cannot pass without a chip."""
import os
import signal
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn_server(extra_args, env_overrides):
    rfd, wfd = os.pipe()
    env = {**os.environ, "PYTHONPATH": REPO, **env_overrides}
    proc = subprocess.Popen(
        [sys.executable, "-m", "redisson_tpu.server", "--port", "0",
         "--ready-fd", str(wfd), *extra_args],
        pass_fds=(wfd,), env=env, cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    os.close(wfd)
    with os.fdopen(rfd, "rb") as ready:
        line = ready.readline().split()
    assert line and line[0] == b"READY", line
    return proc, line[1].decode(), int(line[2])


def _resp(sock, *args) -> bytes:
    sock.sendall(b"*%d\r\n" % len(args) + b"".join(
        b"$%d\r\n%s\r\n" % (len(a), a) for a in args))
    head = b""
    while not head.endswith(b"\r\n"):
        head += sock.recv(1)
    assert head[:1] == b"$", head
    want = int(head[1:]) + 2
    body = b""
    while len(body) < want:
        body += sock.recv(want - len(body))
    return body[:-2]


def test_platform_flag_wins_and_sigterm_releases_with_client_connected():
    """--platform cpu beats an inherited JAX_PLATFORMS (a supervisor relies
    on it to keep N children off the one chip), INFO says where the server
    runs, and SIGTERM with an idle client still connected exits 0 inside a
    bound — before the fix wait_closed() waited on the client forever."""
    proc, host, port = _spawn_server(
        ["--platform", "cpu"], {"JAX_PLATFORMS": "tpu"})
    try:
        sock = socket.create_connection((host, port), timeout=30)
        info = _resp(sock, b"INFO").decode()
        assert "# Device" in info
        fields = dict(
            ln.split(":", 1) for ln in info.splitlines() if ":" in ln)
        assert fields["platform"] == "cpu"
        assert fields["device_kind"] and int(fields["local_device_count"]) >= 1
        assert fields["device0"].startswith("id=")
        assert fields["replica_occupancy"] == "none"
        assert fields["host_colocations"] == "0"
        assert fields["wire_plane"] in ("native", "python")
        # a server process sets the collector for a heap of records
        # (server.GC_THRESHOLDS); an embedded ServerThread does not
        assert fields["gc_thresholds"] == "50000,20,100"
        proc.send_signal(signal.SIGTERM)  # `sock` stays open and idle
        assert proc.wait(timeout=15) == 0
        sock.close()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


def test_compile_cache_dir_has_one_rule(monkeypatch):
    import redisson_tpu

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert redisson_tpu.compile_cache_dir() == "/some/dir"  # any platform
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert redisson_tpu.compile_cache_dir() is None  # hermetic CPU: no cache
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert redisson_tpu.compile_cache_dir() == os.path.join(REPO, ".jax_cache")
    monkeypatch.delenv("JAX_PLATFORMS")
    assert redisson_tpu.compile_cache_dir() == os.path.join(REPO, ".jax_cache")


@pytest.mark.parametrize("env_dir", ["/some/dir", None])
def test_enable_compile_cache_sets_a_directory_only_without_the_env_var(env_dir):
    """With JAX_COMPILATION_CACHE_DIR set, no directory is set in code (jax
    reads the variable itself); without it, <checkout>/.jax_cache."""
    code = (
        "import jax, redisson_tpu\n"
        "keys = []\n"
        "real = jax.config.update\n"
        "jax.config.update = lambda k, v: (keys.append(k), real(k, v))\n"
        "print(redisson_tpu.enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print('jax_compilation_cache_dir' in keys)\n"
    )
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "tpu,cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=120, check=True,
    ).stdout.split()
    want = env_dir or os.path.join(REPO, ".jax_cache")
    assert out == [want, want, str(env_dir is None)]


def test_old_cache_knobs_are_gone():
    # spelled in pieces so that this file is not itself a hit
    gone = ["REDISSON_TPU_" + "COMPILE_CACHE", "RTPU_" + "COMPILE_CACHE",
            "redisson_tpu" + "_xla"]
    out = subprocess.run(
        ["git", "grep", "-l", *[a for g in gone for a in ("-e", g)], "--",
         ":!ISSUE.md"],
        cwd=REPO, capture_output=True, text=True,
    )
    assert out.stdout == "", out.stdout


def test_word_count_propagates_a_device_error(monkeypatch):
    """A kernel the device refuses must surface — the old bare-except
    returned the HOST answer, and a chip run would have reported it as a
    device number."""
    import redisson_tpu
    from redisson_tpu.client.codec import StringCodec
    from redisson_tpu.core import kernels as K
    from redisson_tpu.services import mapreduce as MR

    vals = ["alpha beta alpha"] * 50
    assert MR.device_word_count(vals) == {"alpha": 100, "beta": 50}

    def refuse(*_a, **_k):
        raise RuntimeError("INTERNAL: injected sort failure")

    monkeypatch.setattr(K, "wc_sort_runs", refuse)
    before = dict(MR.WC_ANSWERED)
    with pytest.raises(RuntimeError, match="injected sort failure"):
        MR.device_word_count(vals)
    c = redisson_tpu.create()
    try:
        m = c.get_map("bringup:wc", codec=StringCodec())
        m.put_all({f"d{i}": v for i, v in enumerate(vals)})
        with pytest.raises(RuntimeError, match="injected sort failure"):
            MR.word_count(m)
    finally:
        c.shutdown()
    assert dict(MR.WC_ANSWERED) == before  # neither pipeline answered


def test_word_count_counter_names_the_pipeline_that_answered():
    from redisson_tpu.services import mapreduce as MR

    before = dict(MR.WC_ANSWERED)
    MR.device_word_count(["a b a"] * 10)
    assert MR.WC_ANSWERED["device"] == before.get("device", 0) + 1
    # more distinct words than d_max: the documented semantic fallback
    MR.device_word_count([f"w{i}" for i in range(600)], d_max_bits=8)
    assert MR.WC_ANSWERED["host"] == before.get("host", 0) + 1


def test_compile_failure_is_fatal_not_retryable():
    """XLA compile errors are worded INTERNAL:/... like a failed launch;
    tagged where jax raises them they stop being -TRYAGAIN material."""
    import jax
    import jax.numpy as jnp
    from jax._src import compiler as jax_compiler

    from redisson_tpu.core import ioplane, kernels

    assert kernels._tag_compile_failure in jax_compiler._XLA_RUNTIME_ERROR_HANDLERS

    @jax.jit
    def refused(x):
        return jax.ffi.ffi_call(
            "rtpu_no_such_target", jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

    with pytest.raises(ioplane.KernelCompileError) as ei:
        refused(jnp.arange(4.0))
    assert not ioplane.is_retryable_device_fault(ei.value)
    # same words from the RUNTIME stay retryable; compile-time text is kept
    assert ioplane.is_retryable_device_fault(RuntimeError("INTERNAL: launch failed"))
    assert not ioplane.is_retryable_device_fault(
        ioplane.KernelCompileError("INTERNAL: Mosaic failed to compile"))


def test_failed_placement_fails_the_install():
    from redisson_tpu.core.store import DeviceStore, StateRecord

    store = DeviceStore()

    def hook(_name, _rec):
        raise RuntimeError("RESOURCE_EXHAUSTED: device 3 is full")

    store.placement_hook = hook
    with pytest.raises(RuntimeError, match="device 3 is full"):
        store.put("x", StateRecord(kind="bloom"))
    assert not store.peek("x")


def test_supervisor_refuses_to_hand_one_chip_to_many_children(monkeypatch):
    from redisson_tpu.cluster import supervisor as S

    monkeypatch.setattr(S, "_local_tpu_chips", lambda: 1)
    sup = S.ClusterSupervisor(masters=2, env={"JAX_PLATFORMS": "tpu,cpu"})
    with pytest.raises(S.NodeStartupError, match="one process per chip"):
        sup.start()
    assert sup.nodes() == []  # failed at spawn, nothing started
    for ok in (S.ClusterSupervisor(masters=2, platform="cpu"),
               S.ClusterSupervisor(masters=1, env={"JAX_PLATFORMS": "tpu"})):
        ok._check_one_process_per_chip()
    monkeypatch.setattr(S, "_local_tpu_chips", lambda: 0)
    S.ClusterSupervisor(masters=2, env={"JAX_PLATFORMS": ""})._check_one_process_per_chip()


def test_native_artifact_is_keyed_by_source_content(tmp_path, monkeypatch):
    from redisson_tpu.net import _native

    src = tmp_path / "resp.cpp"
    src.write_text("// one\n")
    monkeypatch.setattr(_native, "_SRC_PATH", str(src))
    one = _native.so_path()
    os.utime(src, (1, 1))  # a copied tree rewrites mtimes: must not matter
    assert _native.so_path() == one
    src.write_text("// two\n")
    assert _native.so_path() != one
    src.unlink()
    assert _native.so_path() is None


def test_collectives_avoid_unsigned_narrow_max():
    """XLA:TPU miscomputes max/min all-reduces over uint8/uint16 (chip run,
    PR 21).  The cross-device merge must still be exact on uint8 inputs and
    hand back uint8."""
    import jax

    from redisson_tpu.parallel.manager import merge_across_devices

    devs = jax.devices()[:4]
    rng = np.random.default_rng(0)
    host = [rng.integers(0, 255, 4096).astype(np.uint8) for _ in devs]
    out = merge_across_devices([jax.device_put(h, d) for h, d in zip(host, devs)])
    assert out.dtype == np.uint8
    assert np.array_equal(np.asarray(out), np.maximum.reduce(host))


def _run_smoke(*flags):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *flags],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )


def test_chip_smoke_without_a_chip_fails_and_prints_no_result():
    p = _run_smoke()
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr


def test_chip_smoke_rehearsal_passes_but_is_never_a_pass():
    import json

    p = _run_smoke("--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    report = json.loads(p.stdout.strip().splitlines()[-2][len("REPORT "):])
    emb = report["phases"]["embedded"]
    assert emb["config4"]["answered_by"] == "device"
    srv = report["phases"]["served"]["server"]
    assert srv["host_colocations"] == 0 and srv["replica_occupancy"] is None
    assert srv["coalesced_calls"]["bf.mexists64.coalesced"] >= 1
    assert report["phases"]["served"]["sigterm_exit_seconds"] < 15
