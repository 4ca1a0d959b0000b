"""The rounds ``_decode_spec`` runs with the model's own prediction module
as the drafter (``engine/engine.py`` ``_run_spec_rounds``; ``models/joyai.py``),
at ``joyai-test`` size on the CPU.

On seeded weights the module is accepted at chance, so acceptance is HANDED
IN here: an oracle stands where the module's drafter stands
(``cfg.drafter()``; tests only) and drafts, after a position and the token
served there, the plain greedy continuation's next token (accepted) or
another (rejected).
Whatever the drafts were, what a request is served is the undrafted greedy
continuation, token for token: the stop, the counters and the step records
are held to what happened.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from runbookai_tpu.engine.engine import EngineConfig, EngineCore
from runbookai_tpu.engine.request import EngineRequest, SamplingParams
from runbookai_tpu.models import joyai
from runbookai_tpu.models.llama import CONFIGS
from runbookai_tpu.utils.tokens import ByteTokenizer

CFG = CONFIGS["joyai-test"]
PS, SEED, ROUNDS, SLOTS, MAX_POS = 16, 11, 4, 4, 256
# (length, seed): rows of unlike lengths
PROMPTS = [(50, 3), (60, 4), (41, 5)]

ACCEPT = {
    "none": lambda row, pos: False,
    "all": lambda row, pos: True,
    # unlike per row and along a row: pairs, singles and a reject after an accept
    "mixed": lambda row, pos: (pos + row) % 3 != 0,
}


@dataclasses.dataclass(frozen=True)
class OracleConfig(joyai.JoyaiConfig):
    """``joyai-test`` whose drafts come from a table ``[position][token
    there]`` -> the draft of the token after it. The production drafter sees
    the module's output alone, so the oracle's module passes stamp what they
    were fed (the token after each position, and the position) into two
    channels of that output, and its ``draft_tokens`` reads them back."""

    table: tuple = ()

    def drafter(self):
        module_pass, module_pass_ragged, _ = super().drafter()
        table = np.asarray(self.table, np.int32)

        def stamp(y, tokens, positions):
            return y.at[..., 0].set(tokens.astype(y.dtype)).at[..., 1].set(
                (positions + 1).astype(y.dtype))

        def stamped(params, cfg, hidden, tokens, positions, *rest):
            y, *out = module_pass(params, cfg, hidden, tokens, positions, *rest)
            return (stamp(y, tokens, positions), *out)

        def stamped_ragged(params, cfg, hidden, tokens, positions, *rest):
            y, *out = module_pass_ragged(params, cfg, hidden, tokens, positions, *rest)
            return (stamp(y, tokens, positions), *out)

        def draft_tokens(params, cfg, y):
            tok, pos = y[..., 0].astype(jnp.int32), y[..., 1].astype(jnp.int32)
            return jnp.asarray(table)[jnp.clip(pos, 0, MAX_POS - 1),
                                      jnp.clip(tok, 0, table.shape[1] - 1)]

        return stamped, stamped_ragged, draft_tokens


@pytest.fixture(scope="module")
def params():
    from runbookai_tpu.models import hf_loader

    return hf_loader.load_or_init("joyai-test", None, seed=SEED, dtype=jnp.float32)[1]


def _ids(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, size=n)]


def _engine(params, cfg=CFG, **over):
    ecfg = dict(page_size=PS, num_pages=128, max_batch_slots=SLOTS, prefill_chunk=32,
                max_seq_len=512, block_pages=2, speculative=True, kv_dtype=jnp.float32,
                decode_steps_per_dispatch=ROUNDS, mixed_dispatch=False)
    ecfg.update(over)
    return EngineCore(cfg, params, ByteTokenizer(), EngineConfig(**ecfg), seed=0)


def _serve(core, max_new, **sampling):
    """The three prompts, submitted together: they prefill side by side.
    Returns (requests, streamed tokens)."""
    sampling.setdefault("stop_token_ids", ())
    reqs, streams = [], []
    for i, (n, seed) in enumerate(PROMPTS):
        req = EngineRequest(request_id=f"r{i}", prompt_ids=_ids(n, seed),
                            sampling=SamplingParams(temperature=0.0, max_new_tokens=max_new,
                                                    **sampling))
        streams.append([])
        req.on_token = streams[-1].append
        reqs.append(req)
        core.submit(req)
    while core.has_work:
        core.step()
    return reqs, streams


@pytest.fixture(scope="module")
def plain(params):
    """Undrafted greedy decoding of the three prompts, 40 tokens each."""
    reqs, _ = _serve(_engine(params, speculative=False), 40)
    return [list(r.prompt_ids) + list(r.out_ids) for r in reqs]


def _oracle(plain, accept) -> OracleConfig:
    """Keyed by (position, the greedy token there): the rows' continuations
    never share both (asserted), so the key names the row."""
    table = np.zeros((MAX_POS, CFG.vocab_size), np.int64)
    seen = {}
    for row, seq in enumerate(plain):
        for pos in range(1, len(seq) - 1):
            nxt = seq[pos + 1]
            draft = nxt if accept(row, pos + 1) else (nxt + 1) % 256
            assert seen.setdefault((pos, seq[pos]), draft) == draft, (row, pos)
            table[pos, seq[pos]] = draft
    return OracleConfig(**{**dataclasses.asdict(CFG), "name": "joyai-oracle"},
                        table=tuple(map(tuple, table.tolist())))


def _expected(plain, accept, stops) -> tuple[int, int]:
    """(drafted, accepted) by the rule of a round: with ``n`` tokens
    committed the draft on hand is of token ``n``; the round serves token
    ``n`` and, if the draft was right and the row has not stopped, token
    ``n + 1`` too."""
    drafted = accepted = 0
    for row, stop in enumerate(stops):
        n = PROMPTS[row][0] + 1  # the prefill served the first token
        while n < stop:
            drafted += 1
            if accept(row, n) and n + 1 < stop:
                accepted += 1
                n += 1
            n += 1
    return drafted, accepted


@pytest.mark.parametrize("max_new", [12, 21], ids=["max12", "max21"])
@pytest.mark.parametrize("mode", list(ACCEPT))
def test_rounds_serve_plain_greedy_decoding_token_for_token(params, plain, mode, max_new):
    """Acceptance 0, 1 and mixed per row; ``max_tokens`` lands on the first
    token of a round (the accepted second one is dropped) or on its second."""
    core = _engine(params, _oracle(plain, ACCEPT[mode]))
    reqs, streams = _serve(core, max_new)
    for r, stream, seq in zip(reqs, streams, plain):
        n = len(r.prompt_ids)
        assert list(r.out_ids) == seq[n:n + max_new] == stream
    stops = [len(r.prompt_ids) + max_new for r in reqs]
    drafted, accepted = _expected(plain, ACCEPT[mode], stops)
    m = core.metrics
    assert (m["spec_drafted"], m["spec_accepted"]) == (drafted, accepted)
    assert m["decode_tokens"] == drafted + accepted == 3 * (max_new - 1)
    if mode == "none":
        assert accepted == 0
    if mode == "all":
        assert accepted == 3 * ((max_new - 1) // 2)
    if mode == "mixed":
        assert 0 < accepted < drafted
    recs = [s for s in core.flight.snapshot() if "spec" in s]
    assert recs and all("_decode_spec" in s["program"] and s["spec"]["rounds"] == ROUNDS
                        and s["k"] == ROUNDS and s["spec"]["rows"] == s["rows"]
                        for s in recs)
    assert sum(s["spec"]["drafted"] for s in recs) == drafted
    assert sum(s["spec"]["accepted"] for s in recs) == accepted
    # one fetch a dispatch: a row's rounds come back together
    assert m["decode_dispatches"] == len(recs)
    assert max(s["spec"]["drafted"] for s in recs) > len(reqs)  # several rounds a dispatch


@pytest.mark.parametrize("mode", ["none", "all", "mixed"])
def test_a_stop_lands_on_the_same_token(params, plain, mode):
    """A stop token and a stop string, each first met mid-dispatch: the
    rows end where undrafted decoding ends them, and nothing after the stop
    is served even where a round had accepted it."""
    n0, n2 = PROMPTS[0][0], PROMPTS[2][0]
    stop_token = plain[0][n0 + 6]
    # the first printable ASCII byte row 2 serves past its fourth token
    stop_string = next(chr(t) for t in plain[2][n2 + 4:] if 32 < t < 127)
    kw = dict(stop_token_ids=(stop_token,), stop_strings=(stop_string,))
    want, _ = _serve(_engine(params, speculative=False), 30, **kw)
    assert {r.finish_reason.value for r in want} >= {"stop_token", "stop_string"}
    assert sorted(len(r.out_ids) for r in want)[1] > 4  # stops fired mid-dispatch
    core = _engine(params, _oracle(plain, ACCEPT[mode]))
    got, streams = _serve(core, 30, **kw)
    for w, g, stream in zip(want, got, streams):
        assert list(g.out_ids) == list(w.out_ids) == stream
        assert g.finish_reason == w.finish_reason
    drafted, accepted = _expected(
        plain, ACCEPT[mode], [len(r.prompt_ids) + len(r.out_ids) for r in got])
    assert (core.metrics["spec_drafted"], core.metrics["spec_accepted"]) == (drafted, accepted)
    assert not core.kv.seqs  # every page came back


@pytest.mark.parametrize("mixed", [False, True], ids=["split", "mixed"])
def test_rows_that_join_mid_flight_and_rows_that_sample(params, plain, mixed):
    """A greedy row decodes in rounds; a prompt joins (its chunks ride mixed
    steps, where decode rows go undrafted, or split prefill steps) and a
    SAMPLING row decodes beside it (the batch then takes the undrafted
    multi-step path, which keeps the module's cache and draft current).
    The greedy rows are served plain greedy decoding throughout."""
    core = _engine(params, _oracle(plain, ACCEPT["mixed"]), mixed_dispatch=mixed)
    greedy = [EngineRequest(request_id=f"r{i}", prompt_ids=_ids(*PROMPTS[i]),
                            sampling=SamplingParams(temperature=0.0, max_new_tokens=36,
                                                    stop_token_ids=())) for i in (0, 1)]
    hot = EngineRequest(request_id="hot", prompt_ids=_ids(40, 9), sampling=SamplingParams(
        temperature=0.8, seed=5, max_new_tokens=9, stop_token_ids=()))
    core.submit(greedy[0])
    core.step()
    core.submit(greedy[1])
    while greedy[1].slot is None:
        core.step()
    assert [r.slot for r in greedy] == [0, 1]  # the oracle's table is by slot
    core.submit(hot)
    core.run_until_idle()
    for r, seq in zip(greedy, plain):
        n = len(r.prompt_ids)
        assert list(r.out_ids) == seq[n:n + 36]
    assert len(hot.out_ids) == 9
    m = core.metrics
    assert m["spec_accepted"] > 0 and (m["mixed_steps"] > 0) == mixed
    programs = {p for s in core.flight.snapshot() if "experts" in s
                for p in s["experts"]["programs"]}
    assert "_decode_spec" in programs and programs & {"_decode_multi", "_decode_step"}


def test_no_round_is_run_past_the_sequence_limit(params, plain):
    """A round takes up to two positions: near ``max_seq_len`` the dispatch
    runs fewer rounds, then hands the last token to the undrafted step."""
    n = len(_ids(*PROMPTS[0]))
    core = _engine(params, _oracle(plain, ACCEPT["all"]), max_seq_len=n + 11,
                   max_batch_slots=1)
    req = EngineRequest(request_id="r0", prompt_ids=_ids(*PROMPTS[0]),
                        sampling=SamplingParams(temperature=0.0, max_new_tokens=40,
                                                stop_token_ids=()))
    core.submit(req)
    core.run_until_idle()
    assert list(req.out_ids) == plain[0][n:n + len(req.out_ids)]
    assert n + len(req.out_ids) == n + 11  # served up to the limit, not past it
    assert {s["spec"]["rounds"] for s in core.flight.snapshot() if "spec" in s} >= {4, 1}
