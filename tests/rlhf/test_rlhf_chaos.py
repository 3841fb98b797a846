"""RLHF chaos: the two failure modes the subsystem must absorb.

1. A rollout generator task is SIGKILLed mid-round AFTER an in-flight
   weight sync landed. The streaming owner's lineage resubmission
   replays the task on a fresh worker; because the rollout is
   deterministic in its arguments (greedy decode from version-stamped
   packed weights, syncs applied and awaited at fixed block
   boundaries), the replayed prefix reproduces the SAME tokens with the
   SAME per-token policy-version stamps, and per-uid dedup delivers
   each block exactly once.

2. Weight syncs are raced against live decode on an in-process engine
   fleet: swaps land between decode steps (never draining the batch),
   version stamps stay monotone per trajectory, and trajectories that
   finished entirely on the original weights are bit-identical to a
   sync-free reference round.
"""

import json
import os
import tempfile
import threading
import time

import numpy as np
import pytest

pytestmark = [pytest.mark.rlhf, pytest.mark.chaos]

#: tiny CPU transformer shared by both tests
MODEL = dict(vocab_size=64, d_model=16, n_layers=2, n_heads=2,
             head_dim=8, d_ff=32, max_seq_len=64, rotary_dim=8,
             dtype="float32", remat_policy="none")
ENGINE = dict(decode_slots=2, kv_block_size=4, max_seq_len=64,
              prefill_chunk=8)


def _tiny_params(seed=0):
    import jax
    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.serve.llm_engine import _resolve_dtype
    m = dict(MODEL)
    m["dtype"] = _resolve_dtype(m["dtype"])
    return init_params(TransformerConfig(**m), jax.random.PRNGKey(seed))


@pytest.mark.slow
@pytest.mark.streaming
def test_midround_sigkill_replays_blocks_exactly_once_with_stamps(
        rlhf_cluster):
    """SIGKILL one rollout worker at block 3 — one block AFTER its
    in-flight sync to version 5 at block 2. Lineage replay must redo
    the whole sync chain (stage v3 → blocks 0-1 → sync v5 → blocks
    2-3): every block arrives exactly once, and tokens AND per-token
    version stamps are bit-identical to a fault-free reference run."""
    import jax

    from ray_tpu.rlhf.rollout import make_rlhf_rollout_streams
    from ray_tpu.rlhf.weight_sync import pack_weights
    from ray_tpu.rllib.rollout_stream import (RolloutBlockStream,
                                              block_uid)

    params = _tiny_params()
    packed_v3 = pack_weights(params, 3, block_size=64)
    packed_v5 = pack_weights(
        jax.tree.map(lambda x: x * 1.1, params), 5, block_size=64)

    workers, blocks, max_new = 2, 4, 8
    suffixes = [[[2 + (w * 16 + b * 3 + k) % 60 for k in range(4)]
                 for b in range(blocks)] for w in range(workers)]
    system_prompt = list(range(2, 18))
    syncs = {w: {2: packed_v5} for w in range(workers)}

    def _run(faults):
        gens = make_rlhf_rollout_streams(
            MODEL, ENGINE, packed_v3, suffixes, system_prompt,
            max_new, syncs=syncs, faults=faults)
        stream = RolloutBlockStream(gens, collect=True)
        for _ in stream.iter_blocks(timeout=600):
            pass
        return stream

    ref = _run(faults=None)
    expect = {i["uid"]: (b["tokens"], b["versions"])
              for b, i in zip(ref.blocks, ref.infos)}
    assert len(expect) == workers * blocks

    marker = tempfile.mktemp()
    got = _run(faults={0: {"die_at_block": 3, "marker": marker}})
    assert os.path.exists(marker), "worker never died — test vacuous"

    assert sorted(got.delivered_uids()) == sorted(
        block_uid(w, b) for w in range(workers) for b in range(blocks)), \
        "blocks not delivered exactly once after mid-round kill"
    for batch, info in zip(got.blocks, got.infos):
        rtoks, rvers = expect[info["uid"]]
        assert np.array_equal(batch["tokens"], rtoks), \
            f"replayed tokens diverged for uid {info['uid']}"
        assert np.array_equal(batch["versions"], rvers), \
            f"replayed version stamps diverged for uid {info['uid']}"
        # the sync chain itself: pre-sync blocks stamped v3, post v5
        want = 3 if info["block"] < 2 else 5
        assert info["versions"] == [want], info


def test_weight_sync_raced_against_decode_keeps_versions_consistent():
    """Publish int8 refreshes from another thread while a round is
    mid-decode: swaps land between steps with ZERO decode stall,
    per-token stamps are monotone within every trajectory and only
    ever name published versions, and any trajectory decoded entirely
    on the original weights is bit-identical to a sync-free round."""
    from ray_tpu.rlhf.config import RLHFConfig
    from ray_tpu.rlhf.rollout import RolloutEngine
    from ray_tpu.rlhf.weight_sync import WeightPublisher

    cfg = RLHFConfig(placement="anakin", num_engines=1,
                     max_new_tokens=12, system_prompt=tuple(range(2, 18)),
                     prompt_len=22, model=MODEL,
                     engine=dict(decode_slots=4, kv_block_size=4,
                                 prefill_chunk=8))
    suffixes = [[2 + (j * 5 + k) % 60 for k in range(4)]
                for j in range(8)]
    params = _tiny_params(seed=cfg.seed)

    # reference: same round, no syncs
    ref_engine = RolloutEngine(cfg, params=params)
    ref_stream = ref_engine.stream_round(suffixes, collect=True)
    ref_tokens = {}
    for batch, info in ref_stream.iter_blocks(timeout=300):
        ref_tokens[info["shard_key"]] = batch["tokens"]
    ref_engine.shutdown()

    rollout = RolloutEngine(cfg, params=params)
    eng = rollout.engines[0]
    pub = WeightPublisher(rollout.engines,
                          block_size=cfg.quant_block_size)
    stream = rollout.stream_round(suffixes, collect=True)

    def swapped_in(version, timeout_s=60.0):
        # the engine holds ONE staged refresh and swaps it in at the
        # top of a tick with no program out: a second publish staged
        # before that replaces the first and the two count as one swap.
        # Blocks can land several at once, so let each publish land
        # before the next (decode goes on meanwhile: no slot drains)
        deadline = time.monotonic() + timeout_s
        while eng.stats()["weight_version"] < version:
            assert time.monotonic() < deadline, "the swap never landed"
            time.sleep(0.002)

    # race: a publish fires the moment each of the first 3 blocks
    # lands, while the other trajectories are still mid-decode
    results = []
    for batch, info in stream.iter_blocks(timeout=300):
        results.append((batch, info))
        if pub.stats()["publishes"] < 3:
            t = threading.Thread(target=pub.publish, args=(params,))
            t.start()
            t.join()
            swapped_in(pub.version)
    assert pub.stats()["publishes"] >= 3

    stamped = set()
    for batch, info in results:
        vers = batch["versions"][0]
        assert len(vers) == cfg.max_new_tokens
        assert (np.diff(vers) >= 0).all(), \
            f"version stamps regressed within a trajectory: {vers}"
        stamped |= set(int(v) for v in vers)
        if set(vers.tolist()) == {0}:
            # finished before any swap: original weights, so the
            # raced round must not have perturbed its decode
            assert np.array_equal(batch["tokens"],
                                  ref_tokens[info["shard_key"]]), \
                "sync race corrupted a version-0 trajectory"
    assert stamped <= set(range(pub.version + 1)), stamped
    assert max(stamped) >= 1, \
        "no token ever decoded under a synced version — race vacuous"

    s = eng.stats()
    assert s["weight_swaps"] == pub.stats()["publishes"]
    assert s["weight_version"] == pub.version
    assert s["sync_stall_s"] == 0.0, \
        f"in-flight sync stalled decode for {s['sync_stall_s']}s"
    rollout.shutdown()


# -------------------------------------------------- chaos soak leg
@pytest.mark.slow
@pytest.mark.streaming
@pytest.mark.parametrize(
    "seed",
    [int(s) for s in os.environ.get(
        "RAY_TPU_CHAOS_SOAK_SEEDS", "1101").split(",")])
def test_rlhf_rollout_chaos_soak(seed):
    """The chaos-matrix rlhf leg: a 2-worker rollout fleet streams
    version-stamped blocks under 5% message drops/dups/delays while a
    seeded-random worker is SIGKILLed at a seeded-random block AFTER
    its in-flight weight sync; exactly-once delivery and bit-identical
    tokens + per-token version stamps are asserted against a same-args
    reference run (rollouts are deterministic in their arguments, so
    the reference is exact even under the message-level chaos)."""
    import jax

    import ray_tpu
    from ray_tpu.core import chaos
    from ray_tpu.rlhf.rollout import make_rlhf_rollout_streams
    from ray_tpu.rlhf.weight_sync import pack_weights
    from ray_tpu.rllib.rollout_stream import (RolloutBlockStream,
                                              block_uid)

    ray_tpu.shutdown()
    os.environ[chaos.ENV_SEED] = str(seed)
    os.environ[chaos.ENV_CONFIG] = json.dumps(
        {"drop_prob": 0.05, "dup_prob": 0.05, "delay_prob": 0.05,
         "delay_s": 0.05})
    rng = np.random.default_rng(seed)
    workers, blocks, max_new = 2, 4, 8
    sync_block = 2
    victim = int(rng.integers(0, workers))
    die_at = int(rng.integers(1, blocks))   # ≥1 block already streamed
    suffixes = [[[int(t) for t in rng.integers(2, 62, size=4)]
                 for _ in range(blocks)] for _ in range(workers)]
    marker = tempfile.mktemp()
    try:
        ray_tpu.init(num_cpus=8, _num_initial_workers=4)
        params = _tiny_params(seed=seed % 7)
        packed_v3 = pack_weights(params, 3, block_size=64)
        packed_v5 = pack_weights(
            jax.tree.map(lambda x: x * 1.1, params), 5, block_size=64)
        syncs = {w: {sync_block: packed_v5} for w in range(workers)}
        system_prompt = list(range(2, 18))

        def _run(faults):
            gens = make_rlhf_rollout_streams(
                MODEL, ENGINE, packed_v3, suffixes, system_prompt,
                max_new, syncs=syncs, faults=faults)
            stream = RolloutBlockStream(gens, collect=True)
            for _ in stream.iter_blocks(timeout=600):
                pass
            return stream

        ref = _run(faults=None)
        expect = {i["uid"]: (b["tokens"], b["versions"])
                  for b, i in zip(ref.blocks, ref.infos)}
        got = _run(faults={victim: {"die_at_block": die_at,
                                    "marker": marker}})
        assert os.path.exists(marker), \
            f"victim {victim} never died (seed={seed})"
        assert sorted(got.delivered_uids()) == sorted(
            block_uid(w, b)
            for w in range(workers) for b in range(blocks)), \
            f"not exactly-once (seed={seed}, victim={victim}, " \
            f"die_at={die_at})"
        for batch, info in zip(got.blocks, got.infos):
            rtoks, rvers = expect[info["uid"]]
            assert np.array_equal(batch["tokens"], rtoks), \
                f"tokens diverged (seed={seed}, uid={info['uid']})"
            assert np.array_equal(batch["versions"], rvers), \
                f"stamps diverged (seed={seed}, uid={info['uid']})"
            want = 3 if info["block"] < sync_block else 5
            assert info["versions"] == [want], (seed, info)
    finally:
        os.environ.pop(chaos.ENV_SEED, None)
        os.environ.pop(chaos.ENV_CONFIG, None)
        ray_tpu.shutdown()
