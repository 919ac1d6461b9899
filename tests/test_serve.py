"""Serving engine: output parity vs. naive full-forward generation, HOL
mitigation via chunked prefill, slot allocation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, tiny_config
from repro.core import TPU_V5E, TPU_V5P, device_model
from repro.models import build_model
from repro.serve import Engine, EngineConfig, SlotAllocator
from repro.serve.kvcache import Sequence

CFG = tiny_config(get_config("qwen3-1.7b")).with_overrides(attn_impl="reference")


def greedy_reference(cfg, params, prompt, max_new):
    """Ground truth: re-run the FULL forward for every generated token."""
    model = build_model(cfg)
    toks = list(prompt)
    for _ in range(max_new):
        logits, _ = model.forward(
            params, {"tokens": jnp.asarray([toks], jnp.int32)})
        toks.append(int(np.argmax(np.asarray(logits)[0, -1])))
    return toks[len(prompt):]


@pytest.mark.parametrize("mode", ["serial", "interference_aware"])
def test_engine_matches_full_forward(mode):
    eng = Engine(CFG, ecfg=EngineConfig(max_slots=2, max_len=96,
                                        prefill_chunk=16, mode=mode),
                 dev=TPU_V5E)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, CFG.vocab_size, size=n).tolist()
               for n in (9, 23)]
    ids = [eng.submit(p, max_new=4) for p in prompts]
    metrics = eng.run_until_done()
    for i, p in zip(ids, prompts):
        want = greedy_reference(CFG, eng.params, p, 4)
        assert metrics[i]["output"] == want, (mode, i)


@pytest.mark.parametrize("kind, model", [("TPU v5 lite", TPU_V5E),
                                         ("TPU v5", TPU_V5P)])
def test_device_model_maps_attached_kind(kind, model):
    assert device_model(kind) is model


def test_device_model_refuses_unknown_kind_and_engine_needs_dev():
    """The engine prices for the chip it runs on: an unknown
    ``device_kind`` is an error, and there is no default model."""
    with pytest.raises(ValueError, match="TPU v6 lite"):
        device_model("TPU v6 lite")
    with pytest.raises(TypeError, match="dev"):
        Engine(CFG)


def test_engine_continuous_batching_over_subscription():
    """More requests than slots: all must finish via slot recycling."""
    eng = Engine(CFG, ecfg=EngineConfig(max_slots=2, max_len=64,
                                        prefill_chunk=16), dev=TPU_V5E)
    rng = np.random.default_rng(1)
    ids = [eng.submit(rng.integers(1, 50, size=8).tolist(), max_new=3)
           for _ in range(5)]
    m = eng.run_until_done()
    assert sorted(m) == sorted(ids)
    assert all(v["new_tokens"] == 3 for v in m.values())


def test_chunked_prefill_reduces_decode_gap():
    """Paper §4.2: a long prompt must not block the decode batch — the
    interference-aware mode splits it into chunks, so the number of
    decode steps interleaved during the long prefill is > 0."""
    def interleavings(mode):
        eng = Engine(CFG, ecfg=EngineConfig(max_slots=2, max_len=320,
                                            prefill_chunk=32, mode=mode,
                                            tbt_slo_ms=1e-6), dev=TPU_V5E)
        eng.submit([1, 2, 3, 4], max_new=40)     # decoder workload
        for _ in range(4):                        # let it start decoding
            eng.step()
        eng.submit(list(range(1, 257)), max_new=2)  # long prompt arrives
        kinds = []
        for _ in range(40):
            n0 = len(eng.events)
            eng.step()
            kinds += [e.kind for e in eng.events[n0:]]
        # count decodes between first and last prefill chunk
        first = kinds.index("prefill_chunk") if "prefill_chunk" in kinds else 0
        last = len(kinds) - 1 - kinds[::-1].index("prefill_chunk") \
            if "prefill_chunk" in kinds else 0
        return kinds[first:last].count("decode"), kinds.count("prefill_chunk")

    serial_interleave, serial_chunks = interleavings("serial")
    aware_interleave, aware_chunks = interleavings("interference_aware")
    assert serial_chunks == 1                    # monolithic prefill
    assert aware_chunks > 1                      # chunked
    assert aware_interleave > serial_interleave  # decode kept flowing


def test_pick_chunk_prices_floor_chunk(monkeypatch):
    """The halving ladder must include the 16-token floor as a PRICED
    candidate (the old loop stopped above it), and the no-candidate-
    passes fallback must be estimator-backed: the priced candidate with
    the lowest predicted TBT, not an unpriced halving."""
    import repro.serve.engine as engine_mod

    eng = Engine(CFG, ecfg=EngineConfig(max_slots=2, max_len=96,
                                        prefill_chunk=64,
                                        tbt_slo_ms=1e-9),
                 dev=TPU_V5E)   # nothing passes
    priced_chunks = []
    real_solve = engine_mod.solve_scenarios

    def spy(scenarios, dev=None):
        priced_chunks.append(
            [int(sc.background[0].name.removeprefix("prefill"))
             for sc in scenarios])
        return real_solve(scenarios, dev)

    monkeypatch.setattr(engine_mod, "solve_scenarios", spy)
    seq = Sequence(0, prompt_len=80, max_new=1)
    chunk = eng._pick_chunk(seq, n_active_decodes=1)
    assert priced_chunks and priced_chunks[-1] == [64, 32, 16]
    # the estimator-backed fallback: with TBT monotone in chunk size the
    # minimum predicted TBT is the floor chunk — and it was priced
    assert chunk == 16

    # with a sane SLO the largest passing candidate wins as before
    eng.ecfg.tbt_slo_ms = 1e9
    assert eng._pick_chunk(seq, n_active_decodes=1) == 64


def test_pick_chunk_short_remainder_still_priced(monkeypatch):
    """Prompts shorter than twice the floor used to skip pricing
    entirely (empty candidate ladder); now the floor chunk is priced."""
    import repro.serve.engine as engine_mod

    eng = Engine(CFG, ecfg=EngineConfig(max_slots=2, max_len=96,
                                        prefill_chunk=64), dev=TPU_V5E)
    priced = []
    real_solve = engine_mod.solve_scenarios

    def spy(scenarios, dev=None):
        priced.append(
            [int(sc.background[0].name.removeprefix("prefill"))
             for sc in scenarios])
        return real_solve(scenarios, dev)

    monkeypatch.setattr(engine_mod, "solve_scenarios", spy)
    seq = Sequence(0, prompt_len=20, max_new=1)
    chunk = eng._pick_chunk(seq, n_active_decodes=1)
    assert priced == [[20, 16]]  # the floor chunk was estimator-priced
    assert chunk in (20, 16)


def test_slot_allocator():
    a = SlotAllocator(n_slots=2, max_len=32)
    s1 = Sequence(1, prompt_len=8, max_new=4)
    s2 = Sequence(2, prompt_len=8, max_new=4)
    s3 = Sequence(3, prompt_len=8, max_new=4)
    huge = Sequence(4, prompt_len=40, max_new=4)
    assert a.can_admit(s1) and a.admit(s1) in (0, 1)
    assert a.can_admit(s2)
    a.admit(s2)
    assert not a.can_admit(s3)          # full
    assert not a.can_admit(huge)        # never fits
    a.release(1)
    assert a.can_admit(s3)


def test_slot_allocator_admit_when_full_raises():
    a = SlotAllocator(n_slots=1, max_len=32)
    a.admit(Sequence(1, prompt_len=8, max_new=4))
    with pytest.raises(RuntimeError):
        a.admit(Sequence(2, prompt_len=8, max_new=4))
    # the failed admit must not leak state
    assert a.utilization == 1.0 and list(a.active) == [1]


def test_slot_allocator_double_release_raises():
    a = SlotAllocator(n_slots=2, max_len=32)
    a.admit(Sequence(1, prompt_len=8, max_new=4))
    a.release(1)
    with pytest.raises(KeyError):
        a.release(1)
    with pytest.raises(KeyError):
        a.release(99)                       # never admitted
    # free list must not grow from failed releases
    assert len(a.free) == 2 and a.utilization == 0.0


def test_slot_allocator_can_admit_respects_max_len():
    a = SlotAllocator(n_slots=4, max_len=16)
    assert a.can_admit(Sequence(1, prompt_len=8, max_new=8))    # == max_len
    assert not a.can_admit(Sequence(2, prompt_len=8, max_new=9))  # one over
    with pytest.raises(RuntimeError):
        a.admit(Sequence(3, prompt_len=20, max_new=0))


def test_slot_allocator_utilization_round_trip():
    a = SlotAllocator(n_slots=4, max_len=32)
    seqs = [Sequence(i, prompt_len=4, max_new=4) for i in range(3)]
    slots = [a.admit(s) for s in seqs]
    assert len(set(slots)) == 3
    assert a.utilization == pytest.approx(0.75)
    assert a.active_slots().tolist() == sorted(slots)
    a.release(1)
    assert a.utilization == pytest.approx(0.5)
    assert a.active_slots().tolist() == sorted(s for i, s in
                                               zip(range(3), slots) if i != 1)
    a.release(0)
    a.release(2)
    assert a.utilization == 0.0 and a.active_slots().tolist() == []


def test_pick_chunk_degraded_mode_is_conservative():
    """Fleet hook: in degraded mode (device oversubscribed after a fleet
    failure) the scheduler must stop taking the largest passing chunk
    and always pick the minimum-predicted-TBT candidate; with TBT
    monotone in chunk size that is the floor chunk. The idle-batch 4x
    chunk boost is also disabled."""
    eng = Engine(CFG, ecfg=EngineConfig(max_slots=2, max_len=96,
                                        prefill_chunk=64,
                                        tbt_slo_ms=1e9),
                 dev=TPU_V5E)   # everything passes
    seq = Sequence(0, prompt_len=80, max_new=1)
    assert eng._pick_chunk(seq, n_active_decodes=1) == 64
    assert eng._pick_chunk(seq, n_active_decodes=0) == 80

    eng.set_degraded(True, reason="fleet: dev oversubscribed")
    assert eng._pick_chunk(seq, n_active_decodes=1) == 16
    assert eng._pick_chunk(seq, n_active_decodes=0) == 64  # no 4x boost
    assert eng.events[-1].kind == "degraded"

    eng.set_degraded(False)
    eng.set_degraded(False)            # idempotent: no duplicate event
    assert eng._pick_chunk(seq, n_active_decodes=1) == 64
    assert [e.kind for e in eng.events[-2:]] == ["degraded", "recovered"]


def _host_draw(row, temperature, seed):
    """One row drawn on the host, slot by slot as the engine always drew:
    softmax at ``temperature``, a generator seeded with ``seed``."""
    p = np.exp((row - row.max()) / temperature)
    p /= p.sum()
    return int(np.random.default_rng(seed).choice(len(p), p=p))


@pytest.mark.parametrize("batch", [5, 1], ids=["decode_B1V", "extend_11V"])
def test_device_pick_is_host_argmax_lowest_index_on_ties(batch):
    """The greedy pick on the device reads what ``np.argmax`` reads over
    the host copy of the same logits, ties included."""
    eng = Engine(CFG, ecfg=EngineConfig(max_slots=batch, max_len=32),
                 dev=TPU_V5E)
    V = CFG.vocab_size
    logits = np.random.default_rng(7).standard_normal(
        (batch, 1, V)).astype(np.float32)
    top = logits.max() + 1.0
    logits[0, 0, [3, 11, V - 1]] = top           # a three-way tie
    if batch > 1:
        logits[1, 0, [V - 2, V - 1]] = top       # a tie at the far end
        logits[2, 0, :] = 0.5                    # every index ties
    ids = eng._sample(jnp.asarray(logits))
    assert ids.shape == (batch, 1)
    np.testing.assert_array_equal(ids, np.argmax(logits, axis=-1))
    assert ids[0, 0] == 3
    if batch > 1:
        assert ids[1, 0] == V - 2 and ids[2, 0] == 0


@pytest.mark.parametrize("batch", [4, 1], ids=["decode_B1V", "extend_11V"])
def test_sampled_pick_keeps_the_host_formula(batch):
    """Above temperature 0 every row is drawn on the host exactly as the
    per-slot formula drew it, so a fixed seed serves the same tokens."""
    ecfg = EngineConfig(max_slots=batch, max_len=32, temperature=0.7,
                        seed=11)
    eng = Engine(CFG, ecfg=ecfg, dev=TPU_V5E)
    logits = 3.0 * np.random.default_rng(5).standard_normal(
        (batch, 1, CFG.vocab_size)).astype(np.float32)
    ids = eng._sample(jnp.asarray(logits))
    want = [[_host_draw(logits[b, 0], ecfg.temperature, ecfg.seed)]
            for b in range(batch)]
    assert ids.tolist() == want
    assert eng._greedy._cache_size() == 0        # the device never picked


@pytest.mark.parametrize("temperature, pick", [(0.0, "device"),
                                               (0.8, "host")])
def test_decode_events_say_where_the_token_was_picked(temperature, pick):
    """Every decode step records its pick; a greedy run over a changing
    number of decoding slots compiles the pick for two shapes at most."""
    eng = Engine(CFG, ecfg=EngineConfig(max_slots=3, max_len=64,
                                        prefill_chunk=16,
                                        temperature=temperature),
                 dev=TPU_V5E)
    rng = np.random.default_rng(2)
    want = {eng.submit(rng.integers(1, 50, size=n).tolist(), max_new=new):
            new for n, new in ((5, 9), (12, 3), (30, 6), (7, 2))}
    m = eng.run_until_done()
    assert {i: v["new_tokens"] for i, v in m.items()} == want
    decodes = [e.detail for e in eng.events if e.kind == "decode"]
    assert len({d["batch"] for d in decodes}) > 1
    assert decodes and all(d["pick"] == pick for d in decodes)
    assert eng._greedy._cache_size() == (2 if pick == "device" else 0)
