"""Runtime substrate tests: checkpoint atomicity/integrity, bit-exact
resume, straggler detection, token pipeline determinism, elastic remesh."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.data.tokens import TokenPipeline
from repro.launch.train import train
from repro.runtime import checkpoint as ckpt
from repro.runtime.resilience import StepDeadline


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.ones((2,), jnp.bfloat16),
                  "d": jnp.array(3, jnp.int32)}}
    d = str(tmp_path)
    ckpt.save(d, 10, tree)
    restored, step = ckpt.restore(d, jax.tree.map(jnp.zeros_like, tree))
    assert step == 10
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_keeps_last_and_latest(tmp_path):
    d = str(tmp_path)
    for s in [1, 2, 3, 4, 5]:
        ckpt.save(d, s, {"x": jnp.array(s)}, keep_last=2)
    assert ckpt.latest_step(d) == 5
    steps = sorted(int(p[5:]) for p in os.listdir(d) if p.startswith("step_"))
    assert steps == [4, 5]


def test_checkpoint_corruption_detected(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, {"x": jnp.arange(100.0)})
    path = os.path.join(d, "step_0000000001", "state.msgpack.zst")
    raw = bytearray(open(path, "rb").read())
    raw[10] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with pytest.raises(IOError):
        ckpt.restore(d, {"x": jnp.zeros(100)})


def test_uncommitted_checkpoint_invisible(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 7, {"x": jnp.array(1)})
    os.remove(os.path.join(d, "step_0000000007", "COMMITTED"))
    assert ckpt.latest_step(d) is None


def test_token_pipeline_deterministic_and_host_sharded():
    p1 = TokenPipeline(vocab=100, seq_len=16, global_batch=8, seed=3)
    a, b = p1.batch(5), p1.batch(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = p1.batch(6)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    # host sharding partitions the batch
    h0 = TokenPipeline(vocab=100, seq_len=16, global_batch=8, seed=3,
                       host_id=0, num_hosts=2)
    assert h0.host_batch == 4


def test_train_resume_bit_exact(tmp_path):
    """Crash/restart must reproduce the uninterrupted run exactly."""
    cfg = configs.reduce_for_smoke(configs.get_config("yi-34b"))
    d = str(tmp_path)
    _, full = train(cfg, steps=8, global_batch=2, seq_len=16,
                    ckpt_dir=None, log=lambda *a: None)
    # interrupted run: crash after step 4 -> fresh process resumes
    train(cfg, steps=8, global_batch=2, seq_len=16, ckpt_dir=d,
          ckpt_every=4, crash_at=4, log=lambda *a: None)
    _, tail = train(cfg, steps=8, global_batch=2, seq_len=16, ckpt_dir=d,
                    ckpt_every=100, resume="auto", log=lambda *a: None)
    np.testing.assert_allclose(tail, full[4:], rtol=1e-5)


def test_straggler_detection():
    sd = StepDeadline(k=6.0, floor_s=0.0)
    for _ in range(20):
        assert not sd.observe(0.10 + np.random.default_rng(0).normal() * 0.0)
    assert sd.observe(5.0)          # 50x the median -> straggler
    assert sd.stragglers == 1
    assert sd.deadline < 5.0


def test_gradient_compression_preserves_signal():
    from repro.optim import ef_compress_update
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=512), jnp.float32)
    err = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    for _ in range(50):  # same gradient repeatedly: EF must converge to it
        s, err = ef_compress_update(g, err, frac=0.05)
        acc = acc + s
    # accumulated transmitted mass approximates 50*g direction-wise
    cos = float(jnp.dot(acc, g) / (jnp.linalg.norm(acc) * jnp.linalg.norm(g)))
    assert cos > 0.97


# ------------------------------------------------------ blobstore integrity
def _tiny_store(tmp_path):
    from repro.scenarios.cache import ResultCache
    from repro.sim import SimResult
    store = ResultCache(str(tmp_path / "store"))
    res = SimResult(fcts=np.arange(8, dtype=np.float64),
                    slowdowns=np.ones(8), wall_time=0.5, backend="stub")
    return store, res


def test_blobstore_every_truncation_is_a_quarantined_miss(tmp_path):
    """No prefix of a blob may decode: every truncation point must read
    as a miss and quarantine the file aside for forensics."""
    store, res = _tiny_store(tmp_path)
    path = store.put("k" * 16, res)
    data = open(path, "rb").read()
    for cut in range(len(data)):
        open(path, "wb").write(data[:cut])
        assert store.get("k" * 16) is None, f"truncation at {cut} decoded"
        assert os.path.exists(path + ".corrupt")
        assert not os.path.exists(path)
    open(path, "wb").write(data)            # full bytes restore cleanly
    got = store.get("k" * 16)
    np.testing.assert_array_equal(got.fcts, res.fcts)


def test_blobstore_bitflip_is_a_quarantined_miss(tmp_path):
    store, res = _tiny_store(tmp_path)
    path = store.put("f" * 16, res)
    data = bytearray(open(path, "rb").read())
    for pos in (0, 5, len(data) // 2, len(data) - 1):    # magic/digest/body
        flipped = bytearray(data)
        flipped[pos] ^= 0x01
        open(path, "wb").write(bytes(flipped))
        assert store.get("f" * 16) is None, f"bit flip at {pos} decoded"
        assert os.path.exists(path + ".corrupt")


def test_blobstore_legacy_entry_still_reads(tmp_path):
    """Pre-envelope entries (raw compressed msgpack, no RBS1 header)
    decode best-effort so an old cache survives the upgrade."""
    import msgpack
    from repro.runtime.blobstore import _compress
    store, res = _tiny_store(tmp_path)
    path = store._path("l" * 16)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    raw = msgpack.packb(store._encode(res), use_bin_type=True)
    open(path, "wb").write(_compress(raw))
    got = store.get("l" * 16)
    np.testing.assert_array_equal(got.fcts, res.fcts)
    np.testing.assert_array_equal(got.slowdowns, res.slowdowns)


def test_blobstore_crash_atomicity_under_sigkill(tmp_path):
    """SIGKILL a writer at arbitrary points mid-put: readers must see
    either nothing or the complete, verifiable value — never a torso."""
    import signal
    import subprocess
    import sys
    import time as _time
    root = str(tmp_path / "store")
    key = "c" * 16
    child = (
        "import sys, numpy as np\n"
        "from repro.scenarios.cache import ResultCache\n"
        "from repro.sim import SimResult\n"
        "store = ResultCache(sys.argv[1])\n"
        "res = SimResult(fcts=np.arange(300000, dtype=np.float64),\n"
        "                slowdowns=np.arange(300000, dtype=np.float64),\n"
        "                wall_time=1.0, backend='stub')\n"
        "while True:\n"
        "    store.put(sys.argv[2], res)\n")
    from repro.scenarios.cache import ResultCache
    store = ResultCache(root)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    for round_i in range(6):
        proc = subprocess.Popen([sys.executable, "-c", child, root, key],
                                env=env, stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        _time.sleep(0.4 + 0.037 * round_i)      # land at varied offsets
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        got = store.get(key)
        if got is not None:     # all-or-nothing: full value or clean miss
            np.testing.assert_array_equal(
                got.fcts, np.arange(300000, dtype=np.float64))
        # integrity layer never quarantined a *committed* blob
        assert not os.path.exists(store._path(key) + ".corrupt")


# ----------------------------------------------------------------- leasing
def test_leasedir_claim_is_exclusive(tmp_path):
    from repro.runtime.blobstore import LeaseDir
    leases = LeaseDir(str(tmp_path / "leases"))
    assert leases.claim("t1", "w0:100")
    assert not leases.claim("t1", "w1:101")     # filesystem arbitration
    body = leases.owner("t1")
    assert body["owner"] == "w0:100" and body["pid"] == os.getpid()
    age0 = leases.age("t1")
    assert age0 is not None and age0 < 5.0
    leases.heartbeat("t1")
    assert leases.age("t1") <= age0 + 0.1
    assert leases.active() == ["t1"]
    leases.release("t1")
    assert not leases.held("t1") and leases.age("t1") is None
    assert leases.claim("t1", "w1:101")         # released -> reclaimable
    leases.release("t1")
    leases.release("t1")                        # idempotent
    leases.heartbeat("t1")                      # no-op on broken lease


# ----------------------------------------------------------- retry policy
def test_backoff_deterministic_capped_and_desynchronized():
    from repro.runtime.resilience import Backoff
    b = Backoff(base_s=0.5, factor=2.0, cap_s=4.0, jitter=0.5, seed=3)
    # deterministic: same (seed, token, attempt) -> same delay
    assert b.delay(2, "taskA") == b.delay(2, "taskA")
    # desynchronized: same attempt, different tokens -> different delays
    assert b.delay(2, "taskA") != b.delay(2, "taskB")
    # jitter only shaves: delay in ((1-jitter)*raw, raw]
    for attempt, raw in [(1, 0.5), (2, 1.0), (3, 2.0), (4, 4.0), (9, 4.0)]:
        d = b.delay(attempt, "t")
        assert 0.5 * raw < d <= raw, (attempt, d)
    # a different seed reshuffles the jitter
    assert Backoff(seed=4).delay(1, "t") != Backoff(seed=3).delay(1, "t")


def test_classify_error_taxonomy():
    from repro.runtime.resilience import classify_error

    class TransientBackendError(Exception):
        retryable = True

    assert classify_error(OSError("disk hiccup"))
    assert classify_error(IOError("alias of OSError"))
    assert classify_error(TimeoutError("deadline"))
    assert classify_error(ConnectionError("reset"))
    assert classify_error(MemoryError())
    assert classify_error(TransientBackendError("says so"))
    assert not classify_error(ValueError("bad shape"))
    assert not classify_error(TypeError("bad arg"))
    assert not classify_error(RuntimeError("logic bug"))
    assert not classify_error(NotImplementedError())


# --------------------------------------------------- checkpoint rollback
def test_restore_latest_loadable_rolls_back_past_corruption(tmp_path):
    d = str(tmp_path)
    for s in (1, 2, 3):
        ckpt.save(d, s, {"x": jnp.full((50,), float(s))}, keep_last=3)
    blob3 = os.path.join(d, "step_0000000003", "state.msgpack.zst")
    raw = bytearray(open(blob3, "rb").read())
    raw[10] ^= 0xFF
    open(blob3, "wb").write(bytes(raw))
    tree, step, skipped = ckpt.restore_latest_loadable(
        d, {"x": jnp.zeros(50)})
    assert step == 2
    np.testing.assert_array_equal(np.asarray(tree["x"]), np.full(50, 2.0))
    assert len(skipped) == 1 and skipped[0][0] == 3
    assert "hash" in skipped[0][1] or "IOError" in skipped[0][1]
    # plain restore still hard-fails on the corrupt newest step
    with pytest.raises(IOError):
        ckpt.restore(d, {"x": jnp.zeros(50)})
    # corrupt everything -> explicit FileNotFoundError naming the reasons
    for s in (1, 2):
        blob = os.path.join(d, f"step_000000000{s}", "state.msgpack.zst")
        raw = bytearray(open(blob, "rb").read())
        raw[10] ^= 0xFF
        open(blob, "wb").write(bytes(raw))
    with pytest.raises(FileNotFoundError, match="no loadable committed"):
        ckpt.restore_latest_loadable(d, {"x": jnp.zeros(50)})


# ------------------------------------------------------- compile cache
@pytest.fixture()
def cache_config():
    """Restore JAX's cache directory (and its once-per-process cache
    state) after a test points it somewhere."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_compile_cache_env_stays_in_charge(monkeypatch, tmp_path,
                                           cache_config):
    from repro.runtime import compile_cache
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_persistent_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_checkout(monkeypatch, cache_config):
    from repro.runtime import compile_cache
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    # a fixed path: the same on every call and in every process
    assert compile_cache.use_persistent_cache() == want
    assert compile_cache.use_persistent_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
