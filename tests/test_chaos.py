"""Deterministic chaos suite: kill real engines at seeded fault points
and pin the recovery invariants the crash-tolerance work promises.

Every scenario runs in-process over real ``ContinuousBatcher`` engines
(the same small transformer the migration suite uses) with faults
injected through :mod:`tensorflowonspark_tpu.faults` or by cancelling
the source handle — the in-process stand-in for a replica dying with
its kv pages.  The invariants:

* **byte parity** — a session recovered from its journal (prompt +
  emitted tokens + sampling params) continues byte-identically to the
  uninterrupted solo run, across dense, paged, int8-kv, and
  seeded-sampled engines (the sampling chain is a pure function of
  (seed, ordinal), see ``decode.replay_key``);
* **rollback parity** — a migration that dies mid-pull or mid-install
  rolls back and finishes on the source, still byte-identical;
* **conservation** — a 100-cycle randomized kill/recover loop strands
  zero journal entries and returns every kv page to the pools.

The whole file is marker-gated (``-m chaos``, ``tox -e chaos``) and
seeded via ``CHAOS_SEED`` so CI can run the same schedules on fixed
seeds and a soak box can sweep new ones.
"""
import json
import os
import queue
import random
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu import (faults, fleet, fleet_client, jobs,
                                   kvtransfer, serve)
from tensorflowonspark_tpu.models import decode
from tensorflowonspark_tpu.models.transformer import (Transformer,
                                                      TransformerConfig)

pytestmark = pytest.mark.chaos

CHAOS_SEED = int(os.environ.get("CHAOS_SEED", "0"))


@pytest.fixture(scope="module")
def model_and_params():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_kv_heads=2, n_layers=2, d_ff=64,
                            max_seq_len=32, dtype="float32", rope=True,
                            attention_impl="dense")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _solo(model, params, prompt, n_new, temperature=0.0, seed=0, **kw):
    out = decode.generate(model, params, jnp.asarray([prompt], jnp.int32),
                          max_new_tokens=n_new, loop="host",
                          temperature=temperature,
                          rng=(jax.random.key(seed) if temperature > 0
                               else None), **kw)
    return np.asarray(out)[0].tolist()


def _replay_meta(prompt, emitted, max_new, temp=0.0, seed=0):
    """What a gateway journal entry yields for re-driving: the committed
    sequence and the sampling params — no kv, the dead replica took it."""
    return {"seq": list(prompt) + list(emitted), "plen": len(prompt),
            "max_new": max_new, "remaining": max_new - len(emitted),
            "temp": temp, "seed": seed}


def _snapshot_via_wire(src, frozen):
    """Ship a frozen session through a real PageServer socket (register,
    pull, release) and return what the far side decoded."""
    meta, blocks = kvtransfer.wire_snapshot(frozen, "m",
                                            page_size=src.kv_page_size)
    server = kvtransfer.PageServer()
    try:
        ticket = server.register(meta, blocks)
        return kvtransfer.pull_snapshot(server.addr, ticket)
    finally:
        server.close()


# ------------------------------------------------- mid-decode kills ----

# the acceptance matrix: every kv layout the engines support, plus a
# seeded-sampled session (the case that NEEDS the replay_key chain)
_KILL_KINDS = {
    "dense": (dict(prefill_chunk=8), {}, 0.0, 0),
    "paged": (dict(prefill_chunk=8, kv_page_size=8, kv_pages=24),
              {}, 0.0, 0),
    "int8-kv": (dict(prefill_chunk=8, kv_page_size=8, kv_pages=24,
                     kv_dtype="int8"), {"kv_dtype": "int8"}, 0.0, 0),
    "sampled": (dict(prefill_chunk=8, kv_page_size=8, kv_pages=24),
                {}, 0.8, 11),
}


@pytest.mark.parametrize("kind", sorted(_KILL_KINDS))
def test_mid_decode_kill_replays_byte_identically(model_and_params, kind):
    model, params = model_and_params
    kw, solo_kw, temp, seed = _KILL_KINDS[kind]
    src = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                  **kw)
    dst = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                  **kw)
    journal = fleet.StreamJournal()
    prompt, n_new = [3, 1, 4, 1, 5], 6
    try:
        entry = journal.journal_open({"prompt": prompt, "seed": seed})
        h = src.submit(prompt, n_new, temperature=temp, seed=seed)
        emitted = list(h.tokens.get(timeout=300))   # the tee
        for t in emitted:
            journal.record(entry, t)
        assert 0 < len(emitted) < n_new
        h.cancel()          # the crash: src's kv for this session is gone
        h2, installed = dst.submit_replay(
            _replay_meta(prompt, emitted, n_new, temp=temp, seed=seed))
        assert installed.wait(300), "replay install timed out"
        out = h2.result(timeout=300)
        want = _solo(model, params, prompt, n_new, temperature=temp,
                     seed=seed, **solo_kw)
        assert out == want                          # full byte parity
        # and the splice carried the client-visible prefix verbatim
        assert out[:len(prompt) + len(emitted)] == prompt + emitted
        journal.journal_close(entry)
        assert len(journal) == 0
    finally:
        src.stop()
        dst.stop()


# ------------------------------------------------ mid-prefill kills ----

def test_mid_prefill_kill_fails_loud_and_rerun_matches(model_and_params):
    # a replica dying DURING admission has committed nothing: the
    # correct recovery is a fresh :generate elsewhere, and the dead
    # engine must fail its handles loudly rather than wedge them
    model, params = model_and_params
    kw = dict(prefill_chunk=8, kv_page_size=8, kv_pages=20)
    src = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                  **kw)
    dst = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                  **kw)
    prompt, n_new = [2, 7, 1, 8, 2, 8], 5
    try:
        plan = faults.FaultPlan(CHAOS_SEED).on("serve.admission",
                                               kind="oserror", nth=1)
        with faults.active(plan):
            h = src.submit(prompt, n_new)
            with pytest.raises(OSError, match="injected fault"):
                h.result(timeout=300)
        assert plan.fired == [("serve.admission", "oserror")]
        # the engine died with the admission; later submits fail fast
        with pytest.raises(RuntimeError, match="batcher died"):
            src.submit(prompt, n_new)
        assert dst.submit(prompt, n_new).result(timeout=300) == \
            _solo(model, params, prompt, n_new)
    finally:
        src.stop()
        dst.stop()


# ---------------------------------------------- mid-migration faults ----

def test_mid_migration_pull_fault_retries_then_lands(model_and_params):
    # a transient wire fault mid-pull: the ticket is multi-pull, so the
    # retry re-pulls the SAME snapshot and the migration still lands
    model, params = model_and_params
    kw = dict(prefill_chunk=8, kv_page_size=8, kv_pages=20)
    src = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                  **kw)
    dst = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                  **kw)
    prompt, n_new = [1, 2, 3, 4, 5], 5
    try:
        h = src.submit(prompt, n_new)
        h.tokens.get(timeout=300)                   # live mid-decode
        frozen = src.freeze_session(h, timeout_s=60)
        assert frozen is not None
        meta, blocks = kvtransfer.wire_snapshot(
            frozen, "m", page_size=src.kv_page_size)
        server = kvtransfer.PageServer()
        try:
            ticket = server.register(meta, blocks)
            plan = faults.FaultPlan(CHAOS_SEED).on(
                "kvtransfer.pull", kind="oserror", nth=1, times=1)
            with faults.active(plan):
                with pytest.raises(OSError):
                    kvtransfer.pull_snapshot(server.addr, ticket)
                meta2, blocks2 = kvtransfer.pull_snapshot(server.addr,
                                                          ticket)
            assert plan.fired
        finally:
            server.close()
        h2, installed = dst.submit_resume(meta2, blocks2)
        assert installed.wait(300), "resume install timed out"
        src.complete_migration(frozen)
        assert h2.result(timeout=300) == _solo(model, params, prompt,
                                               n_new)
    finally:
        src.stop()
        dst.stop()


def test_mid_migration_pull_dead_rolls_back_to_source(model_and_params):
    # every pull attempt fails (destination unreachable): the source
    # rolls the frozen session back and finishes it byte-identically
    model, params = model_and_params
    b = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                prefill_chunk=8, kv_page_size=8,
                                kv_pages=20)
    prompt, n_new = [5, 4, 3, 2, 1, 6, 7], 6
    try:
        h = b.submit(prompt, n_new)
        h.tokens.get(timeout=300)
        frozen = b.freeze_session(h, timeout_s=60)
        assert frozen is not None
        meta, blocks = kvtransfer.wire_snapshot(
            frozen, "m", page_size=b.kv_page_size)
        server = kvtransfer.PageServer()
        try:
            ticket = server.register(meta, blocks)
            plan = faults.FaultPlan(CHAOS_SEED).on(
                "kvtransfer.pull", kind="oserror", nth=1, times=None)
            with faults.active(plan):
                for _ in range(2):                  # retries fail too
                    with pytest.raises(OSError):
                        kvtransfer.pull_snapshot(server.addr, ticket)
        finally:
            server.close()
        assert b.rollback_migration(frozen)
        assert h.result(timeout=300) == _solo(model, params, prompt,
                                              n_new)
        assert b.stats()["migrations_completed"] == 0
    finally:
        b.stop()


def test_mid_resume_install_kill_rolls_back_to_source(model_and_params):
    # the destination dies INSTALLING the pulled pages (post-transfer,
    # pre-ack): the splice ack never arrives, so the source still owns
    # the session and rollback must finish it byte-identically
    model, params = model_and_params
    kw = dict(prefill_chunk=8, kv_page_size=8, kv_pages=20)
    src = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                  **kw)
    dst = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                  **kw)
    prompt, n_new = [9, 8, 7, 6, 5], 6
    try:
        h = src.submit(prompt, n_new)
        h.tokens.get(timeout=300)
        frozen = src.freeze_session(h, timeout_s=60)
        assert frozen is not None
        meta2, blocks2 = _snapshot_via_wire(src, frozen)
        plan = faults.FaultPlan(CHAOS_SEED).on("serve.resume_install",
                                               kind="oserror", nth=1)
        with faults.active(plan):
            h2, installed = dst.submit_resume(meta2, blocks2)
            with pytest.raises(OSError, match="injected fault"):
                h2.result(timeout=300)
        assert plan.fired
        assert not installed.is_set()               # no ack: src owns it
        with pytest.raises(RuntimeError, match="batcher died"):
            dst.submit_replay(_replay_meta([1, 2], [3], 1))
        assert src.rollback_migration(frozen)
        assert h.result(timeout=300) == _solo(model, params, prompt,
                                              n_new)
        assert src.stats()["migrations_completed"] == 0
    finally:
        src.stop()
        dst.stop()


# ------------------------------------------- parked-session faults ----

def test_replica_death_with_parked_sessions_redrives_via_journal(
        model_and_params):
    # the scheduler scenario: a replica dies while holding PARKED
    # sessions (frozen snapshots host-side, no device state).  The park
    # sweep fails their handles loudly, so the gateway journal re-drives
    # them on a peer — byte parity, and both pools conserve kv pages.
    model, params = model_and_params
    kw = dict(prefill_chunk=8, kv_page_size=8, kv_pages=24)
    src = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                  **kw)
    dst = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                  **kw)
    journal = fleet.StreamJournal()
    prompt, n_new = [3, 1, 4, 1, 5], 6
    try:
        entry = journal.journal_open({"prompt": prompt})
        h = src.submit(prompt, n_new, priority="batch")
        emitted = list(h.tokens.get(timeout=300))
        parked = src._park_gather(h)         # the controller's move
        assert parked is not None
        src._park_pool.append(parked)
        while True:                          # tokens committed pre-park
            try:                             # all drained to the client
                batch = h.tokens.get(timeout=0.2)
            except queue.Empty:
                break
            if batch is None:
                break
            emitted.extend(batch)
        for t in emitted:
            journal.record(entry, t)
        assert src.stats()["parked_sessions"] == 1
        src.stop()                           # the crash: sweep fails h
        with pytest.raises(RuntimeError):
            h.result(timeout=300)
        # journal re-drive on the peer, byte-identical past the park cut
        h2, installed = dst.submit_replay(
            _replay_meta(prompt, emitted, n_new))
        assert installed.wait(300), "replay install timed out"
        out = h2.result(timeout=300)
        assert out == _solo(model, params, prompt, n_new)
        assert out[:len(prompt) + len(emitted)] == prompt + emitted
        journal.journal_close(entry)
        assert len(journal) == 0
        s = dst.stats()
        assert s["kv_pages_used"] == s["prefix_pages_cached"]
    finally:
        src.stop()
        dst.stop()


def test_park_gather_fault_rolls_back_and_session_completes(
        model_and_params):
    # the snapshot wire-out dies mid-gather: the freeze must ROLL BACK
    # (the migration-lease discipline) and the session finish on its
    # own row byte-identically — a failed park costs nothing
    model, params = model_and_params
    b = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                prefill_chunk=8, kv_page_size=8,
                                kv_pages=24)
    prompt, n_new = [5, 4, 3, 2, 1, 6, 7], 6
    try:
        h = b.submit(prompt, n_new, priority="batch")
        h.tokens.get(timeout=300)            # live mid-decode
        plan = faults.FaultPlan(CHAOS_SEED).on("serve.park_gather",
                                               kind="oserror", nth=1)
        with faults.active(plan):
            with pytest.raises(OSError, match="injected fault"):
                b._park_gather(h)
        assert plan.fired == [("serve.park_gather", "oserror")]
        assert h.result(timeout=300) == _solo(model, params, prompt,
                                              n_new)
        s = b.stats()
        assert s["sessions_parked"] == 0
        assert s["parked_sessions"] == 0
        assert s["kv_pages_used"] == s["prefix_pages_cached"]
    finally:
        b.stop()


def test_park_restore_fault_stays_parked_then_retry_succeeds(
        model_and_params):
    # the resume dies mid-restore: the entry must survive (re-parked for
    # a later retry, exactly what the controller does), and the retry
    # must continue the ORIGINAL client handle byte-identically
    model, params = model_and_params
    b = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                prefill_chunk=8, kv_page_size=8,
                                kv_pages=24)
    prompt, n_new = [9, 8, 7, 6, 5], 6
    try:
        h = b.submit(prompt, n_new, priority="batch")
        emitted = list(h.tokens.get(timeout=300))
        entry = b._park_gather(h)
        assert entry is not None
        plan = faults.FaultPlan(CHAOS_SEED).on("serve.park_restore",
                                               kind="oserror", nth=1)
        with faults.active(plan):
            with pytest.raises(OSError, match="injected fault"):
                b._park_restore(entry)
        assert plan.fired == [("serve.park_restore", "oserror")]
        b._park_restore(entry)               # the retry lands
        out = h.result(timeout=300)          # the ORIGINAL handle
        assert out == _solo(model, params, prompt, n_new)
        assert out[:len(prompt) + len(emitted)] == prompt + emitted
        s = b.stats()
        assert s["sessions_parked"] == 1
        assert s["sessions_unparked"] == 1
        assert s["park_restore_failures"] == 0   # counter is the
        # controller's; the direct probe above raised before submit
        assert s["kv_pages_used"] == s["prefix_pages_cached"]
    finally:
        b.stop()


# ------------------------------------- randomized kill/recover soak ----

def test_kill_recover_cycles_conserve_pool_and_journal(model_and_params):
    # 100 seeded cycles of submit -> (maybe) kill mid-decode -> replay
    # on the peer, with the gateway's StreamJournal as the tee.  After
    # the storm: zero stranded journal entries, every kv page back in
    # both pools (only rc-0 cached prefix pages may stay out of free),
    # and every single stream — killed or not — byte-identical to solo.
    model, params = model_and_params
    kw = dict(prefill_chunk=8, kv_page_size=8, kv_pages=24)
    a = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                **kw)
    b = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                **kw)
    journal = fleet.StreamJournal()
    rng = random.Random(CHAOS_SEED)
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7, 6], [2, 4, 6, 8, 10, 12]]
    n_new = 4
    solos = {}

    def want(prompt, temp, seed):
        key = (tuple(prompt), temp, seed)
        if key not in solos:
            solos[key] = _solo(model, params, prompt, n_new,
                               temperature=temp, seed=seed)
        return solos[key]

    recovered = 0
    try:
        for cycle in range(100):
            src, dst = (a, b) if rng.random() < 0.5 else (b, a)
            prompt = rng.choice(prompts)
            temp, seed = rng.choice([(0.0, 0), (0.7, 5)])
            entry = journal.journal_open({"prompt": prompt, "seed": seed})
            h = src.submit(prompt, n_new, temperature=temp, seed=seed)
            emitted = list(h.tokens.get(timeout=300))
            for t in emitted:
                journal.record(entry, t)
            if rng.random() < 0.6 and len(emitted) < n_new:
                h.cancel()          # replica crash mid-decode
                h2, installed = dst.submit_replay(
                    _replay_meta(prompt, emitted, n_new, temp=temp,
                                 seed=seed))
                assert installed.wait(300), \
                    f"cycle {cycle}: replay install timed out"
                out = h2.result(timeout=300)
                recovered += 1
            else:
                out = h.result(timeout=300)
            assert out == want(prompt, temp, seed), f"cycle {cycle}"
            assert out[:len(prompt) + len(emitted)] == prompt + emitted
            journal.journal_close(entry)
        assert recovered >= 20      # the kill path actually soaked
        assert len(journal) == 0    # zero stranded journal entries
        for eng in (a, b):
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and \
                    eng.stats()["slots_busy"]:
                time.sleep(0.05)
            s = eng.stats()
            assert s["slots_busy"] == 0
            assert s["kv_pages_used"] == s["prefix_pages_cached"]
    finally:
        a.stop()
        b.stop()


# ------------------------------------ host-tier (kvtier) fault sites ----

def _drain_tier(b, timeout=30.0):
    """Wait out the async demote worker (retirement demotes enqueue on
    the device thread after result() fires)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        b._host_tier.flush(5)
        if not b.stats()["slots_busy"]:
            return
        time.sleep(0.01)


def test_host_demote_deny_drops_pages_and_conserves_pool(
        model_and_params):
    # allocation-failure at serve.host_demote: the retiring session's
    # pages are DROPPED instead of demoted — the tier stays empty, the
    # pool stays conserved, and the conversation's next turn simply
    # prefills cold, byte-identically
    model, params = model_and_params
    b = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                prefill_chunk=8, kv_page_size=8,
                                kv_pages=24, host_cache_mb=16)
    prompt, n_new = list(range(1, 19)), 4
    try:
        plan = faults.FaultPlan(CHAOS_SEED).on(
            "serve.host_demote", kind="deny", nth=1, times=None)
        with faults.active(plan):
            cold = b.submit(prompt, n_new).result(timeout=300)
            _drain_tier(b)
            b.drop_prefix_cache()        # eviction demote denied too
            b._host_tier.flush(10)
        assert ("serve.host_demote", "deny") in plan.fired
        assert b._host_tier.stats()["host_pages_cached"] == 0
        assert b._host_tier.stats()["host_demotions"] == 0
        # next turn finds both tiers cold and prefills normally
        s0 = b.stats()
        assert b.submit(prompt, n_new).result(timeout=300) == cold
        s1 = b.stats()
        assert s1["host_hits"] == s0["host_hits"]
        assert (s1["prefill_tokens_shared"]
                == s0["prefill_tokens_shared"])
        assert cold == _solo(model, params, prompt, n_new)
        _drain_tier(b)
        s = b.stats()
        assert s["kv_pages_used"] == s["prefix_pages_cached"]
    finally:
        b.stop()


def test_host_promote_deny_falls_back_to_cold_prefill(model_and_params):
    # allocation-failure at serve.host_promote: a warm host tier reads
    # as cold — the request prefills normally and BYTE-IDENTICALLY,
    # the tier keeps its entries (peek never committed), and the pool
    # stays conserved; with the fault gone the SAME entries promote
    model, params = model_and_params
    b = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                prefill_chunk=8, kv_page_size=8,
                                kv_pages=24, host_cache_mb=16)
    prompt, n_new = list(range(1, 19)), 4
    try:
        cold = b.submit(prompt, n_new).result(timeout=300)
        _drain_tier(b)
        assert b.drop_prefix_cache() > 0
        b._host_tier.flush(10)
        warm_pages = b._host_tier.stats()["host_pages_cached"]
        assert warm_pages >= 2
        plan = faults.FaultPlan(CHAOS_SEED).on(
            "serve.host_promote", kind="deny", nth=1, times=None)
        with faults.active(plan):
            s0 = b.stats()
            denied = b.submit(prompt, n_new).result(timeout=300)
            s1 = b.stats()
        assert ("serve.host_promote", "deny") in plan.fired
        assert denied == cold                 # byte parity through deny
        assert s1["host_hits"] == s0["host_hits"]
        # entries survived the denied lookup; the retry promotes them
        _drain_tier(b)
        assert b.drop_prefix_cache() > 0      # forget the denied run's
        b._host_tier.flush(10)                # re-registered pages
        assert b._host_tier.stats()["host_pages_cached"] >= warm_pages
        s0 = b.stats()
        assert b.submit(prompt, n_new).result(timeout=300) == cold
        s1 = b.stats()
        assert s1["host_hits"] - s0["host_hits"] == 2
        _drain_tier(b)
        s = b.stats()
        assert s["kv_pages_used"] == s["prefix_pages_cached"]
    finally:
        b.stop()


def test_prefix_pull_fault_falls_back_to_local_prefill(model_and_params):
    # the cross-replica kv:prefix pull dies on the wire: the prefetch
    # inserts nothing, counts a failure, and the request falls through
    # to a normal local prefill — byte-identical to the peerless run
    model, params = model_and_params
    mk = lambda: serve.ContinuousBatcher(model, params, n_slots=2,
                                         read_chunk=1, prefill_chunk=8,
                                         kv_page_size=8, kv_pages=24,
                                         host_cache_mb=16)
    a, b = mk(), mk()
    srv = kvtransfer.PageServer(prefix_provider=a.host_prefix_provider)
    prompt, n_new = list(range(1, 19)), 4
    peer = "%s:%d" % (srv.addr[0], srv.addr[1])
    try:
        cold = a.submit(prompt, n_new).result(timeout=300)
        _drain_tier(a)
        assert a._host_tier.stats()["host_pages_cached"] >= 2
        plan = faults.FaultPlan(CHAOS_SEED).on(
            "kvtransfer.prefix_pull", kind="oserror", nth=1)
        with faults.active(plan):
            assert b.prefetch_prefix(peer, prompt) == 0
        assert plan.fired == [("kvtransfer.prefix_pull", "oserror")]
        assert b.counters.get("prefix_pull_failures") == 1
        assert b._host_tier.stats()["host_pages_cached"] == 0
        # the request lands anyway, served by a plain local prefill
        out = b.submit(prompt, n_new).result(timeout=300)
        assert out == cold
        assert b.counters.get("host_hits") == 0
        # with the wire healthy the SAME peer warms the next pull
        # (clear B's tier first: its own retirement just warmed it, and
        # a locally-warm prefix never dials)
        _drain_tier(b)
        b._host_tier.clear()
        assert b.prefetch_prefix(peer, prompt) == 2
        _drain_tier(b)
        s = b.stats()
        assert s["kv_pages_used"] == s["prefix_pages_cached"]
    finally:
        srv.close()
        a.stop()
        b.stop()


# ------------------------------------------------ mega-prompt lane ----
# Long-context serving under chaos: a replica dying mid-stream while a
# mega-prompt's page table is GROWING, and a persistently-denied
# overflow valve.  The lane needs a model whose full-width table
# exceeds the 8-entry seed width (max_seq 128 / page 8 = 16), so these
# build their own instead of using the module fixture.


@pytest.fixture(scope="module")
def long_model_and_params():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_kv_heads=2, n_layers=2, d_ff=64,
                            max_seq_len=128, dtype="float32", rope=True,
                            attention_impl="dense")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _mega_prompt(n=96, seed=7):
    rs = np.random.RandomState(seed)
    return rs.randint(1, 64, n).astype("int32").tolist()


def test_mega_prompt_kill_mid_growth_redrives_byte_identically(
        long_model_and_params):
    # a replica dies INSIDE the table growth a mega-prompt's second
    # chunk forces (its pages would fill the 8-entry table, and a lane
    # row keeps a sink entry past its pages until its last chunk) — one
    # lane chunk already dispatched, zero tokens journaled.  Recovery is the mid-prefill contract: the dead engine
    # fails its handles loudly, and the gateway's journal re-drive (no
    # committed tokens -> a fresh :generate on a peer) replays the
    # whole stream byte-identically through the peer's own lane.
    model, params = long_model_and_params
    kw = dict(prefill_chunk=32, kv_page_size=8, kv_pages=16,
              long_prompt_threshold=24)
    src = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                  **kw)
    dst = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                  **kw)
    journal = fleet.StreamJournal()
    prompt, n_new = _mega_prompt(96), 8
    try:
        entry = journal.journal_open({"prompt": prompt, "seed": 0})
        plan = faults.FaultPlan(CHAOS_SEED).on("serve.table_grow",
                                               kind="oserror", nth=1)
        with faults.active(plan):
            h = src.submit(prompt, n_new)
            with pytest.raises(OSError, match="injected fault"):
                h.result(timeout=300)
        assert plan.fired == [("serve.table_grow", "oserror")]
        # chunks streamed before the kill, but no token ever committed:
        # the stream is the None sentinel alone
        assert src.counters.get("long_chunks_dispatched") >= 1
        assert h.tokens.get_nowait() is None
        # the engine died mid-growth; later submits fail fast
        with pytest.raises(RuntimeError, match="batcher died"):
            src.submit(prompt, n_new)
        out = dst.submit(prompt, n_new).result(timeout=300)
        assert out == _solo(model, params, prompt, n_new)
        st = dst.stats()
        assert st["kv_table_grows"] == 1      # the peer's growth landed
        assert st["long_chunks_dispatched"] >= 3
        journal.journal_close(entry)
        assert len(journal) == 0
    finally:
        src.stop()
        dst.stop()


def test_overflow_demote_deny_fails_typed_and_never_wedges(
        long_model_and_params):
    # the overflow valve is PERSISTENTLY denied: a mega-prompt whose
    # final chunk needs reclaimed pages stalls, and once the replica is
    # otherwise idle it must degrade to a TYPED failure — the
    # KVOverflowError the HTTP handler maps to a retryable 503 — with
    # the engine alive, the pool conserved, and later admissions
    # (short AND long) flowing normally
    model, params = long_model_and_params
    kv_pages = 14
    b = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                prefill_chunk=32, kv_page_size=8,
                                kv_pages=kv_pages, host_cache_mb=16,
                                long_prompt_threshold=24)
    short, prompt, n_new = list(range(1, 19)), _mega_prompt(96), 8
    try:
        # 2 cold cached prefix pages make the valve load-bearing: the
        # mega-prompt's last chunk cannot be covered by the free list
        cold_short = b.submit(short, 4).result(timeout=300)
        assert b.stats()["prefix_pages_cached"] == 2
        plan = faults.FaultPlan(CHAOS_SEED).on(
            "serve.overflow_demote", kind="deny", nth=1, times=None)
        with faults.active(plan):
            h = b.submit(prompt, n_new)
            with pytest.raises(serve.KVOverflowError, match="kv pages"):
                h.result(timeout=300)
        assert ("serve.overflow_demote", "deny") in plan.fired
        assert b.stats()["kv_pages_demoted_overflow"] == 0
        assert issubclass(serve.KVOverflowError, RuntimeError)
        # admission never wedged: the SAME engine keeps serving, and
        # with the fault gone the SAME mega-prompt streams to the end
        assert b.submit(short, 4).result(timeout=300) == cold_short
        out = b.submit(prompt, n_new).result(timeout=300)
        assert out == _solo(model, params, prompt, n_new)
        st = b.stats()
        assert st["kv_pages_demoted_overflow"] >= 1
        assert st["long_prompts_active"] == 0
        # pool conserved: every page back in free or cold-cached
        assert (len(b._free_pages) + len(b._prefix) == kv_pages
                and not any(b._row_pages))
    finally:
        b.stop()


def test_trace_export_deny_never_costs_tokens(model_and_params):
    # the observability plane fails: every span export is denied for
    # the whole run.  The contract is asymmetric on purpose — tracing
    # may lose ALL its spans, serving may lose NOTHING: the traced
    # stream under deny stays byte-identical to solo decode, the drops
    # are counted, and the moment the fault clears the SAME engine
    # records a full lifecycle again
    from tensorflowonspark_tpu import trace

    model, params = model_and_params
    b = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                prefill_chunk=8, kv_page_size=8,
                                kv_pages=24)
    prompt, n_new = [3, 1, 4, 1, 5, 9], 6
    try:
        want = _solo(model, params, prompt, n_new)
        tid = trace.new_id()
        plan = faults.FaultPlan(CHAOS_SEED).on("trace.export",
                                               kind="deny", nth=1,
                                               times=None)
        with faults.active(plan):
            out = b.submit(prompt, n_new,
                           trace_id=tid).result(timeout=300)
        assert ("trace.export", "deny") in plan.fired
        assert out == want                    # byte parity through deny
        assert b.trace.spans(tid) == []       # every span dropped...
        st = b.trace.stats()
        assert st["trace_spans_dropped"] > 0  # ...and counted
        assert st["trace_spans_recorded"] == 0
        # fault cleared: same engine, fresh id, full lifecycle recorded
        tid2 = trace.new_id()
        assert b.submit(prompt, n_new,
                        trace_id=tid2).result(timeout=300) == want
        names = {s["name"] for s in b.trace.spans(tid2)}
        assert {"submit", "queue", "admit", "prefill", "decode",
                "retire"} <= names
        assert b.trace.summary(tid2)["spans"] >= 6
    finally:
        b.stop()


def test_spec_verify_fault_falls_back_byte_identical(model_and_params):
    # the speculation plane fails: every verify-gate probe raises for
    # the whole run.  The contract mirrors trace.export — speculation
    # may lose ALL its speedup, serving may lose NOTHING: under a
    # persistent fault the engine degrades to exactly the non-spec
    # plain path (greedy AND seeded-sampled rows byte-identical to solo
    # decode, fallbacks counted, zero spec rounds), and the moment the
    # fault clears the SAME engine speculates again with unchanged
    # greedy bytes
    model, params = model_and_params
    b = serve.ContinuousBatcher(model, params, n_slots=2, read_chunk=1,
                                prefill_chunk=8, spec_draft="ngram",
                                draft_k=3)
    prompt, n_new = [3, 1, 4, 3, 1, 4], 8
    try:
        want = _solo(model, params, prompt, n_new)
        want_sampled = _solo(model, params, prompt, n_new,
                             temperature=0.9, seed=7)
        plan = faults.FaultPlan(CHAOS_SEED).on("serve.spec_verify",
                                               kind="oserror", nth=1,
                                               times=None)
        with faults.active(plan):
            out = b.submit(prompt, n_new).result(timeout=300)
            out_s = b.submit(prompt, n_new, temperature=0.9,
                             seed=7).result(timeout=300)
        assert ("serve.spec_verify", "oserror") in plan.fired
        assert out == want                    # byte parity through fault
        assert out_s == want_sampled          # plain-path sample parity
        st = b.stats()
        assert st["spec_draft_fallbacks"] > 0  # every round fell back...
        assert st["spec_rounds"] == 0          # ...none speculated
        # fault cleared: same engine speculates again, bytes unchanged
        assert b.submit(prompt, n_new).result(timeout=300) == want
        st = b.stats()
        assert st["spec_rounds"] > 0
        assert st["spec_tokens_proposed"] > 0
    finally:
        b.stop()


# ---------------------------------------------------------------- jobs --
# Bulk-inference jobs under chaos (the TFoS data pump): a replica dying
# mid-partition, the GATEWAY dying mid-job, and checkpoint-write faults
# must all leave the merged output exactly-once — byte-identical to an
# uninterrupted run.  Replicas here are deterministic scoring stubs
# (outputs a pure function of inputs) behind a REAL Gateway; the
# machinery under test is the jobs spool/checkpoint/dispatch contract,
# not the model.


def _wait(pred, timeout=30.0, step=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(step)
    return pred()


def _job_score(prompt):
    return [t * 2 + 1 for t in prompt]


class _ScoreStub:
    """serve.py stand-in whose ``:generate`` outputs are a pure
    function of the inputs, so job output is byte-comparable across
    interrupted and uninterrupted runs."""

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s
        self.idem_keys = []
        self._lock = threading.Lock()
        stub = self

        class _H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def _send(self, code, payload):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path = self.path.rstrip("/") or "/"
                if path in ("/healthz", "/readyz"):
                    self._send(200, {"status": "ok"})
                elif path == "/v1/models/default":
                    self._send(200, {"status": "ok",
                                     "model": {"engine": "stub",
                                               "generate_stats": {}}})
                else:
                    self._send(404, {"error": self.path})

            def do_POST(self):
                length = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(length) or b"{}")
                if not self.path.endswith(":generate"):
                    self._send(404, {"error": self.path})
                    return
                with stub._lock:
                    stub.idem_keys.append(
                        self.headers.get("Idempotency-Key"))
                if stub.delay_s:
                    time.sleep(stub.delay_s)
                self._send(200, {"outputs": [_job_score(p)
                                             for p in req["inputs"]],
                                 "replica": stub.id})

            def log_message(self, fmt, *args):
                pass

        self._server = ThreadingHTTPServer(("127.0.0.1", 0), _H)
        self.host, self.port = self._server.server_address[:2]
        self.id = f"{self.host}:{self.port}"
        threading.Thread(target=self._server.serve_forever,
                         daemon=True).start()

    def close(self):
        self._server.shutdown()
        self._server.server_close()


def _job_gateway(jobs_dir):
    return fleet.Gateway(heartbeat_timeout_s=0.6, monitor_interval_s=0.05,
                         breaker_threshold=2, breaker_cooldown_s=0.3,
                         connect_timeout_s=2.0, replica_timeout_s=10.0,
                         probe_timeout_s=2.0, jobs_dir=str(jobs_dir),
                         job_workers=3, job_checkpoint_every=8)


def _register_stub(gw, stub):
    return fleet_client.register_replica(
        gw.registry_addr, stub.host, stub.port, n_slots=4,
        features={"kv_page_size": 4}, heartbeat_interval_s=0.15)


def _write_job_input(path, n):
    with open(path, "w", encoding="utf-8") as f:
        for i in range(n):
            f.write(json.dumps([(i * 5 + j) % 97 for j in range(3)])
                    + "\n")
    return str(path)


def _job_expected(path, n_partitions):
    """Solo sequential scoring: the bytes a completed job must merge."""
    lines = []
    for p, (s, e) in enumerate(jobs.split_file(path, n_partitions)):
        for off, _nxt, text in jobs.iter_partition(path, s, e):
            body = jobs.record_request(text, {}, "x")
            obj = {"p": p, "offset": off,
                   "outputs": [_job_score(pr) for pr in body["inputs"]]}
            lines.append(json.dumps(obj, sort_keys=True) + "\n")
    return "".join(lines).encode()


def test_job_replica_killed_mid_partition_exactly_once(tmp_path):
    """A replica dying with records in flight costs retries, never
    records: the job completes on the survivor with output identical
    to an uninterrupted sequential scoring."""
    path = _write_job_input(tmp_path / "in.jsonl", 300)
    gw = _job_gateway(tmp_path / "jobs")
    gw.start()
    stubs = [_ScoreStub(delay_s=0.004) for _ in range(2)]
    regs = [_register_stub(gw, s) for s in stubs]
    try:
        cli = fleet_client.FleetClient(*gw.http_addr)
        code, st = cli.submit_job(path, partitions=6, workers=3)
        assert code == 200, st
        assert _wait(lambda: cli.job_status(st["id"])[1]
                     .get("records_done", 0) > 40)
        # kill one replica mid-partition: heartbeat stops (ejection)
        # AND the socket goes away (in-flight dispatches fail)
        regs[0].stop_heartbeat()
        stubs[0].close()
        final = cli.wait_job(st["id"], timeout_s=90.0)
        assert final["state"] == "completed", final
        assert final["records_done"] == 300
        assert final["records_failed"] == 0
        with open(final["output"], "rb") as f:
            assert f.read() == _job_expected(path, 6)
    finally:
        for reg in regs:
            try:
                reg.deregister()
            except Exception:
                pass
        for s in stubs:
            try:
                s.close()
            except Exception:
                pass
        gw.stop()


def test_job_gateway_restart_resumes_from_checkpoint(tmp_path):
    """The gateway itself dying mid-job must not lose the job: durable
    state stays ``running``, and the next gateway's ``--jobs_dir``
    rescan resumes every unfinished partition from its checkpoint —
    merged output still exactly-once."""
    path = _write_job_input(tmp_path / "in.jsonl", 400)
    jobs_dir = tmp_path / "jobs"
    stubs = [_ScoreStub(delay_s=0.004) for _ in range(2)]
    gw1 = _job_gateway(jobs_dir)
    gw1.start()
    regs = [_register_stub(gw1, s) for s in stubs]
    gw2 = None
    try:
        cli = fleet_client.FleetClient(*gw1.http_addr)
        code, st = cli.submit_job(path, partitions=8, workers=3)
        assert code == 200, st
        assert _wait(lambda: cli.job_status(st["id"])[1]
                     .get("records_done", 0) > 60)
        for reg in regs:
            reg.deregister()
        gw1.stop()                      # mid-job death: NOT a cancel

        gw2 = _job_gateway(jobs_dir)    # next gateway life, same spool
        # rescan fires inside start(), before the replicas re-register:
        # widen the retry budget so the resumed workers ride out the
        # registration gap instead of abandoning partitions
        gw2.jobs.record_attempts = 10
        gw2.jobs.partition_attempts = 10
        gw2.start()
        regs = [_register_stub(gw2, s) for s in stubs]
        assert gw2.counters.get("jobs_resumed") == 1
        cli2 = fleet_client.FleetClient(*gw2.http_addr)
        final = cli2.wait_job(st["id"], timeout_s=90.0)
        assert final["state"] == "completed", final
        assert final["records_done"] == 400
        assert final["records_failed"] == 0
        with open(final["output"], "rb") as f:
            assert f.read() == _job_expected(path, 8)
    finally:
        for reg in regs:
            try:
                reg.deregister()
            except Exception:
                pass
        for s in stubs:
            s.close()
        for gw in (gw1, gw2):
            if gw is not None:
                try:
                    gw.stop()
                except Exception:
                    pass


def test_job_checkpoint_fault_bounded_retry_never_completes(tmp_path):
    """A persistently failing checkpoint write is retried a bounded
    number of times, then abandons the partition and fails the JOB —
    it must never mark the job complete over a spool it could not make
    durable.  Once the fault clears, a rescan resumes the job from the
    last durable checkpoint and finishes exactly-once."""
    path = _write_job_input(tmp_path / "in.jsonl", 24)

    def dispatch(body, key):
        return {"outputs": [_job_score(p) for p in body["inputs"]]}

    # nth=2: let submit's job.json write land (the job must EXIST
    # durably), then every checkpoint write after it faults forever
    plan = faults.FaultPlan(CHAOS_SEED).on(
        "jobs.checkpoint_write", "oserror", nth=2, times=None)
    mgr = jobs.JobManager(str(tmp_path / "jobs"), dispatch=dispatch,
                          default_workers=2, checkpoint_every=4,
                          ckpt_attempts=3, partition_attempts=2)
    with faults.active(plan):
        st = mgr.submit({"input": path, "partitions": 2})
        assert _wait(lambda: mgr.status(st["id"])["state"] != "running",
                     timeout=30)
        # join the workers INSIDE the fault window so the state-persist
        # attempt (which must also fail) cannot race the plan teardown
        mgr.stop()
        final = mgr.status(st["id"])
    assert final["state"] == "failed"
    assert final["output"] is None
    assert not os.path.exists(
        os.path.join(mgr.jobs_dir, st["id"], "output.jsonl"))
    assert mgr.counters.get("jobs_ckpt_retries") >= 3   # bounded retry ran
    assert ("jobs.checkpoint_write", "oserror") in plan.fired

    # fault cleared: the durable state is still behind (persist failed
    # too), so a fresh manager resumes and completes exactly-once
    mgr2 = jobs.JobManager(str(tmp_path / "jobs"), dispatch=dispatch,
                           default_workers=2, checkpoint_every=4)
    assert mgr2.rescan() == [st["id"]]
    assert _wait(lambda: mgr2.status(st["id"])["state"] == "completed",
                 timeout=30)
    with open(mgr2.status(st["id"])["output"], "rb") as f:
        assert f.read() == _job_expected(path, 2)
    mgr2.stop()


def _interactive_p95_ms(cli, n=30):
    lats = []
    for _ in range(n):
        t0 = time.monotonic()
        code, _body = cli.generate([[1, 2, 3]], priority="interactive")
        lats.append((time.monotonic() - t0) * 1000.0)
        assert code == 200
    lats.sort()
    return lats[int(0.95 * (len(lats) - 1))]


def test_job_fleet_scale_chaos_byte_identical(tmp_path):
    """The acceptance gate: a >=1000-record job that loses a replica
    mid-run AND the gateway mid-run produces output byte-identical to
    an uninterrupted run — while a concurrent interactive burst's p95
    latency stays bounded (batch-class jobs must not starve the
    interactive class; the same asymmetry test_preemption.py pins on
    the replica scheduler)."""
    path = _write_job_input(tmp_path / "in.jsonl", 1000)

    # ---- uninterrupted reference run --------------------------------
    gw = _job_gateway(tmp_path / "jobs_ref")
    gw.start()
    stubs = [_ScoreStub(delay_s=0.002) for _ in range(2)]
    regs = [_register_stub(gw, s) for s in stubs]
    try:
        cli = fleet_client.FleetClient(*gw.http_addr)
        code, st = cli.submit_job(path, partitions=8, workers=3)
        assert code == 200, st
        ref = cli.wait_job(st["id"], timeout_s=180.0)
        assert ref["state"] == "completed", ref
        with open(ref["output"], "rb") as f:
            ref_bytes = f.read()
    finally:
        for reg in regs:
            try:
                reg.deregister()
            except Exception:
                pass
        for s in stubs:
            s.close()
        gw.stop()
    assert ref_bytes == _job_expected(path, 8)

    # ---- chaos run: replica kill + gateway restart + burst ----------
    jobs_dir = tmp_path / "jobs_chaos"
    stubs = [_ScoreStub(delay_s=0.002) for _ in range(3)]
    gw1 = _job_gateway(jobs_dir)
    gw1.start()
    regs = [_register_stub(gw1, s) for s in stubs]
    gw2 = None
    try:
        cli = fleet_client.FleetClient(*gw1.http_addr)
        idle_p95 = _interactive_p95_ms(cli)     # baseline, fleet idle
        code, st = cli.submit_job(path, partitions=8, workers=3)
        assert code == 200, st
        job_id = st["id"]
        assert _wait(lambda: cli.job_status(job_id)[1]
                     .get("records_done", 0) > 100, timeout=60)
        # interactive burst rides on top of the job at full tilt
        before = cli.job_status(job_id)[1]["records_done"]
        burst_p95 = _interactive_p95_ms(cli)
        after = cli.job_status(job_id)[1]["records_done"]
        assert after > before            # the job really was running
        # replica killed mid-run
        regs[0].stop_heartbeat()
        stubs[0].close()
        assert _wait(lambda: cli.job_status(job_id)[1]
                     .get("records_done", 0) > 400, timeout=60)
        for reg in regs[1:]:
            reg.deregister()
        gw1.stop()                       # gateway killed mid-run

        gw2 = _job_gateway(jobs_dir)
        gw2.jobs.record_attempts = 10
        gw2.jobs.partition_attempts = 10
        gw2.start()
        regs = [_register_stub(gw2, s) for s in stubs[1:]]
        cli2 = fleet_client.FleetClient(*gw2.http_addr)
        final = cli2.wait_job(job_id, timeout_s=180.0)
        assert final["state"] == "completed", final
        assert final["records_done"] == 1000
        assert final["records_failed"] == 0
        with open(final["output"], "rb") as f:
            chaos_bytes = f.read()
        # THE invariant: chaos cost retries and a re-scan, not bytes
        assert chaos_bytes == ref_bytes
        # interactive latency under full batch load stays bounded: the
        # WFQ scheduler spills batch, not interactive (generous CI
        # bound — the relative claim, like test_preemption's
        # armed < disarmed, is what matters)
        assert burst_p95 <= max(10.0 * idle_p95, 1000.0), \
            (burst_p95, idle_p95)
    finally:
        for reg in regs:
            try:
                reg.deregister()
            except Exception:
                pass
        for s in stubs:
            try:
                s.close()
            except Exception:
                pass
        for gw in (gw1, gw2):
            if gw is not None:
                try:
                    gw.stop()
                except Exception:
                    pass
