"""Persistent artifact store + checkpoint/restore tests (core/persist/,
serve/scheduler/checkpoint.py, DESIGN.md §14): codec strictness, atomic
store semantics, cross-process warm boot (zero retraces / zero segment
recompiles), corruption and version-skew degrading to a clean cold start,
eviction-then-reactivation hydrating from disk, engine checkpoint
continuation, and mid-decode scheduler checkpoint exact-token equality
across a process boundary."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import Variable, function, ops
from repro.core.persist import codec
from repro.core.persist.store import ArtifactStore
from repro.core.trace import Aval, FeedRef, Ref, VarRef

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, cache_dir: str, **extra_env) -> dict:
    prog = textwrap.dedent(code)
    env = {**os.environ, "TERRA_CACHE_DIR": cache_dir,
           "PYTHONPATH": os.path.join(ROOT, "src")}
    env.update({k: str(v) for k, v in extra_env.items()})
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ==========================================================================
# codec + store units
# ==========================================================================

def test_namespace_keys_chip_kind_and_git_tracked_sources(tmp_path):
    """The store namespace names the device kind (one TPU generation's
    executables never load on another).  The source digest skips files
    git ignores, yet counts a new module not yet added; inside a
    gitignored copy of the tree (git lists nothing there) every .py file
    counts, so an edit to the copy still moves its key."""
    import re
    import jax
    from repro.core.persist import keys
    kind = re.sub(r"[^A-Za-z0-9]+", "_", jax.devices()[0].device_kind)
    assert f"-{kind}-" in keys.namespace()
    root = tmp_path / "repo"
    copy = root / "copy"
    copy.mkdir(parents=True)
    for d in (root, copy):
        (d / "kept.py").write_text("x = 1\n")
        (d / "new.py").write_text("y = 2\n")
    (root / "built.py").write_text("z = 3\n")
    (root / ".gitignore").write_text("built.py\ncopy/\n")
    assert keys._git_listed(str(root)) is None           # not a work tree
    assert keys.source_files(str(copy), str(copy / "kept.py")) == sorted(
        [str(copy / "kept.py"), str(copy / "new.py")])
    git = ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
    subprocess.run(git + ["init", "-q"], cwd=root, check=True)
    subprocess.run(git + ["add", "kept.py"], cwd=root, check=True)
    assert keys.source_files(str(root), str(root / "kept.py")) == sorted(
        [str(root / "kept.py"), str(root / "new.py")])
    assert keys._git_listed(str(copy)) == set()
    assert keys.source_files(str(copy), str(copy / "kept.py")) == sorted(
        [str(copy / "kept.py"), str(copy / "new.py")])


def test_codec_roundtrip():
    vals = [None, True, 3, -1.5, "s", (1, (2, "x")), [1, [2]],
            {"a": 1, (1, 2): (3,)}, {3, 1, 2}, Aval((2, 3), "float32"),
            Ref(4, 1), FeedRef(2, 0), VarRef(7), slice(1, None, 2),
            Ellipsis, np.dtype("int32"), np.float32(2.5),
            np.arange(6, dtype=np.int64).reshape(2, 3)]
    for v in vals:
        enc = json.loads(json.dumps(codec.encode(v)))   # JSON-native
        dec = codec.decode(enc)
        if isinstance(v, np.ndarray):
            assert np.array_equal(dec, v) and dec.dtype == v.dtype
        else:
            assert dec == v and type(dec) is type(v)


def test_codec_is_strict():
    with pytest.raises(codec.CodecError):
        codec.encode(object())                  # unencodable value
    with pytest.raises(codec.CodecError):
        codec.decode(["nosuchtag", 1])          # unknown tag
    with pytest.raises(codec.CodecError):
        codec.decode(["i"])                     # malformed payload
    with pytest.raises(codec.CodecError):       # oversized array
        codec.encode(np.zeros(1 << 20, np.float32))


def test_store_atomic_and_corrupt(tmp_path):
    st = ArtifactStore(str(tmp_path), "ns")
    assert st.write_json("a/r.json", {"k": [1, 2]}) > 0
    assert st.read_json("a/r.json") == {"k": [1, 2]}
    assert st.read_json("a/absent.json") is None
    # corruption degrades to a miss, never an exception
    with open(os.path.join(str(tmp_path), "ns", "a", "r.json"), "w") as f:
        f.write('{"k": [1,')
    assert st.read_json("a/r.json") is None
    assert st.write_bytes("seg/x.bin", b"\x00\x01") == 2
    assert st.read_bytes("seg/x.bin") == b"\x00\x01"
    st.delete("seg/x.bin")
    assert st.read_bytes("seg/x.bin") is None
    assert "r.json" in st.list("a")


def test_artifacts_written_in_process(tmp_path):
    w = Variable(np.ones(8, np.float32))

    @function(cache_dir=str(tmp_path))
    def step(x):
        y = ops.mul(x, 2.0)
        w.assign(ops.add(w.read(), y))
        return float(ops.reduce_sum(w.read()))

    for i in range(4):
        step(np.full(8, 0.1 * i, np.float32))
    step.wait()
    assert step.stats["artifacts_stored"] > 0
    found = [f for _, _, fs in os.walk(str(tmp_path)) for f in fs]
    assert any(f.endswith(".json") for f in found)      # family record
    step.close()


# ==========================================================================
# cross-process warm boot
# ==========================================================================

TRAIN_PROG = """
    import json
    import numpy as np
    from repro.core import Variable, function, ops

    w = Variable(np.eye(4, dtype=np.float32))

    @function
    def step(x):
        y = ops.matmul(x, w.read())
        w.assign(ops.add(w.read(), ops.mul(y, 0.01)))
        return float(ops.reduce_sum(y))

    outs = [step(np.full((4, 4), i * 0.1, np.float32)) for i in range(8)]
    step.wait()
    st = step.stats
    print(json.dumps({"outs": outs, "retraces": st["retraces"],
                      "recompiled": st["segments_recompiled"],
                      "hits": st["artifact_hits"],
                      "misses": st["artifact_misses"],
                      "warm": st["warm_families"],
                      "aot": st["aot_loads"],
                      "stored": st["artifacts_stored"]}))
    step.close()
"""


@pytest.mark.slow
def test_warmboot_cross_process(tmp_path):
    cold = run_sub(TRAIN_PROG, str(tmp_path))
    warm = run_sub(TRAIN_PROG, str(tmp_path))
    assert cold["stored"] > 0 and cold["warm"] == 0
    # the warm-boot contract: nothing traced, nothing recompiled
    assert warm["retraces"] == 0
    assert warm["recompiled"] == 0
    assert warm["hits"] > 0 and warm["warm"] >= 1 and warm["aot"] >= 1
    assert warm["misses"] == 0
    np.testing.assert_allclose(warm["outs"], cold["outs"], rtol=1e-6)


@pytest.mark.slow
def test_corruption_falls_back_to_cold(tmp_path):
    cold = run_sub(TRAIN_PROG, str(tmp_path))
    # truncate every stored artifact: hydration must degrade to a fresh
    # trace ("slower never wrong"), not crash or load a wrong value
    for root, _, files in os.walk(str(tmp_path)):
        for f in files:
            p = os.path.join(root, f)
            with open(p, "r+b") as fh:
                fh.truncate(os.path.getsize(p) // 2)
    warm = run_sub(TRAIN_PROG, str(tmp_path))
    assert warm["warm"] == 0 and warm["aot"] == 0
    np.testing.assert_allclose(warm["outs"], cold["outs"], rtol=1e-6)


@pytest.mark.slow
def test_version_skew_is_clean_miss(tmp_path):
    cold = run_sub(TRAIN_PROG, str(tmp_path), TERRA_CACHE_SALT="v1")
    skew = run_sub(TRAIN_PROG, str(tmp_path), TERRA_CACHE_SALT="v2")
    assert skew["hits"] == 0 and skew["warm"] == 0      # different namespace
    np.testing.assert_allclose(skew["outs"], cold["outs"], rtol=1e-6)
    warm = run_sub(TRAIN_PROG, str(tmp_path), TERRA_CACHE_SALT="v1")
    assert warm["warm"] >= 1                            # original still hits


# ==========================================================================
# eviction -> reactivation hydrates from disk (satellite fix)
# ==========================================================================

def test_evicted_family_rehydrates(tmp_path):
    @function(cache_dir=str(tmp_path), max_families=1)
    def step(x):
        return float(ops.reduce_sum(ops.mul(x, 3.0)))

    a = np.ones(4, np.float32)
    b = np.ones(8, np.float32)
    for _ in range(3):
        assert step(a) == 12.0
    for _ in range(3):
        assert step(b) == 24.0          # evicts family A -> saved to disk
    before = dict(step.stats)
    for _ in range(3):
        assert step(a) == 12.0          # reactivation hydrates, not traces
    step.wait()
    assert step.stats["warm_families"] - before["warm_families"] >= 1
    assert step.stats["traced_iterations"] == before["traced_iterations"]
    step.close()


# ==========================================================================
# engine checkpoint/restore
# ==========================================================================

def test_engine_checkpoint_continuation(tmp_path):
    w = Variable(np.zeros(4, np.float32))

    def stepfn(x):
        w.assign(ops.add(w.read(), x))
        return float(ops.reduce_sum(w.read()))

    tf1 = function(stepfn)
    feeds = [np.full(4, 0.5, np.float32)] * 4
    for x in feeds:
        tf1(x)
    tf1.save_checkpoint(str(tmp_path / "ck"))
    cont = [tf1(x) for x in feeds]      # the donor's own continuation
    tf1.close()

    tf2 = function(stepfn)              # fresh engine, same Variables
    tf2.restore_checkpoint(str(tmp_path / "ck"))
    resumed = [tf2(x) for x in feeds]
    tf2.wait()
    np.testing.assert_allclose(resumed, cont, rtol=1e-6)
    assert tf2.stats["checkpoint_restores"] == 1
    tf2.close()


def test_engine_restore_raises_on_missing(tmp_path):
    tf = function(lambda x: float(ops.reduce_sum(x)))
    with pytest.raises((OSError, ValueError)):
        tf.restore_checkpoint(str(tmp_path / "nowhere"))
    tf.close()


# ==========================================================================
# scheduler checkpoint: exact continuation across a process boundary
# ==========================================================================

SCHED_PROG = """
    import json, sys, numpy as np, jax
    from repro.configs import smoke_config
    from repro.models import model as M
    from repro.serve.engine import Request
    from repro.serve.scheduler import ContinuousBatchingScheduler

    role, path = sys.argv[1], sys.argv[2]
    cfg = smoke_config("llama3-8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab, 4 + i).astype(np.int32)
               for i in range(6)]
    reqs = [Request(prompt=p, max_new_tokens=10, arrival_time=0.0)
            for p in prompts]

    if role == "ref":
        sch = ContinuousBatchingScheduler(cfg, params, max_slots=4,
                                          max_len=64, temperature=0.0)
        sch.serve(reqs)
        print(json.dumps({"toks": [r.out_tokens for r in reqs]}))
    elif role == "ckpt":
        sch = ContinuousBatchingScheduler(cfg, params, max_slots=4,
                                          max_len=64, temperature=0.0)
        for r in reqs:
            sch.submit(r)
        sch.run(max_steps=7)    # stop mid-decode: 4 in flight, 2 queued
        sch.checkpoint(path)
        assert sch.pool.active_count > 0 and len(sch.queue) > 0
        print(json.dumps({"partial": {r.rid: r.out_tokens or []
                                      for r in reqs}}))
    else:
        sch = ContinuousBatchingScheduler.restore(path, cfg, params)
        partial = json.load(open(path + "/partial.json"))
        tracked = {r.rid: r for _, r in sch.pool.active_items()}
        tracked.update({r.rid: r for r in sch.queue._queue})
        sch.run()
        full = {int(k): v for k, v in partial.items()}
        for rid, r in tracked.items():
            full[rid] = r.out_tokens
        print(json.dumps({"toks": [full[rid] for rid in sorted(full)]}))
    sch.close()
"""


@pytest.mark.slow
def test_scheduler_checkpoint_token_equality(tmp_path):
    ck = str(tmp_path / "sched_ck")

    def run_role(role):
        env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")}
        out = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(SCHED_PROG), role, ck],
            env=env, capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    ref = run_role("ref")
    partial = run_role("ckpt")["partial"]
    with open(ck + "/partial.json", "w") as f:
        json.dump(partial, f)
    resumed = run_role("resume")
    # every request finishes with exactly the tokens the uninterrupted
    # donor would have produced — greedy continuation is bit-identical
    assert resumed["toks"] == ref["toks"]
