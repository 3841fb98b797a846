"""The traffic generator: what the file fixes stays fixed, what the seed
draws is only order, instants and token ids."""
import collections
import itertools
import json
import os

import pytest

from benchmarks import spec, traffic

CHAT = json.load(open(os.path.join(spec.HERE, "traffic", "serve_chat.json")))
DOCQA = json.load(open(os.path.join(spec.HERE, "traffic",
                                    "serve_docqa.json")))
LONGDOC = json.load(open(os.path.join(spec.HERE, "traffic",
                                      "serve_longdoc.json")))
CLOSED = pytest.mark.parametrize("mix", [DOCQA, LONGDOC],
                                 ids=["docqa", "longdoc"])
BIG_SEED = 2**31 + 12345          # more than 32 signed bits hold


def _lengths(plan):
    return collections.Counter((len(r["prompt"]), r["asked"]) for r in plan)


def _window(seed, seconds=40.0):
    """The requests due inside the window (those before it warm the
    queue and are not counted)."""
    plan = traffic.open_loop(CHAT, seed, seconds, 50400)
    assert [r["due"] for r in plan] == sorted(r["due"] for r in plan)
    warm = [r for r in plan if r["due"] < 0]
    assert len(warm) == CHAT["warm_blocks"] * CHAT["lengths"]["strata"]
    assert min(r["due"] for r in plan) >= -traffic.warm_seconds(CHAT)
    return [r for r in plan if r["due"] >= 0]


def test_two_seeds_offer_the_same_multiset_of_lengths():
    a, b = _window(1), _window(BIG_SEED)
    assert len(a) == len(b) == round(40.0 * CHAT["rate_rps"])
    assert _lengths(a) == _lengths(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    # the schedule is the file's: who queues behind whom is not the seed's
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    other = dict(CHAT, schedule_seed=CHAT["schedule_seed"] + 1)
    c = [r for r in traffic.open_loop(other, 1, 40.0, 50400) if r["due"] >= 0]
    assert _lengths(c) == _lengths(a)
    assert [r["due"] for r in c] != [r["due"] for r in a]


def test_every_block_holds_one_request_of_each_stratum():
    strata = CHAT["lengths"]["strata"]
    for seed in (3, BIG_SEED):
        plan = _window(seed)
        for b in range(len(plan) // strata):
            block = plan[b * strata:(b + 1) * strata]
            assert sorted(r["stratum"] for r in block) == list(range(strata))
    # and block b holds the same lengths whatever the seed
    a, b = _window(3), _window(4)
    assert _lengths(a[:strata]) == _lengths(b[:strata])


def test_one_arrival_in_each_interval_of_the_rate():
    rate = CHAT["rate_rps"]
    plan = _window(9)
    for i, r in enumerate(plan):
        assert i / rate <= r["due"] < (i + 1) / rate
        assert r["id"] == i


def test_lengths_follow_the_file_and_stay_inside_its_clips():
    pool = traffic.length_pool(CHAT["lengths"])
    p, a = CHAT["lengths"]["prompt"], CHAT["lengths"]["answer"]
    prompts = [x for s in pool for x, _ in s]
    answers = [y for s in pool for _, y in s]
    assert prompts == sorted(prompts)             # strata are quantile cuts
    assert p["min"] <= min(prompts) and max(prompts) <= p["max"]
    assert a["min"] <= min(answers) and max(answers) <= a["max"]
    mid = sorted(prompts)[len(prompts) // 2]
    assert 0.8 * p["median"] < mid < 1.25 * p["median"]
    # every request fits the engine's window
    assert max(prompts) + max(answers) < CHAT["engine"]["max_seq_len"]


def _first(replay, n):
    return list(itertools.islice(replay, n))


def test_closed_loop_gives_each_client_its_documents_in_turn():
    per_doc, qlen = DOCQA["questions_per_doc"], DOCQA["question_len"]
    for c in range(DOCQA["clients"]):
        ca = _first(traffic.closed_loop(DOCQA, 1, 32768, c), 8 * per_doc)
        cb = _first(traffic.closed_loop(DOCQA, BIG_SEED, 32768, c),
                    8 * per_doc)
        assert [len(r["prompt"]) for r in ca] == [len(r["prompt"]) for r in cb]
        assert [r["asked"] for r in ca] == [r["asked"] for r in cb]
        assert [(r["doc"], r["question"]) for r in ca] == [
            (k, q) for k in range(8) for q in range(per_doc)]
        first, second = ca[0], ca[1]
        n = len(first["prompt"]) - qlen
        assert n in DOCQA["doc_lengths"]
        assert first["prompt"][:n] == second["prompt"][:n]     # the document
        assert first["prompt"][n:] != second["prompt"][n:]     # new question
        assert ca[per_doc]["prompt"][:64] != first["prompt"][:64]
        longest = max(len(r["prompt"]) + r["asked"] for r in ca)
        assert longest < DOCQA["engine"]["max_seq_len"]
        # what the prefix cache can serve: the document, but for the
        # first question of one that set-up's fill has not prefilled
        assert [r["shared"] for r in ca[:2 * per_doc]] == \
            [n] * per_doc + [0] + [len(ca[per_doc]["prompt"]) - qlen] \
            * (per_doc - 1)
        assert ca[0]["prompt"] != cb[0]["prompt"]
    ids = [r["id"] for c in range(DOCQA["clients"])
           for r in _first(traffic.closed_loop(DOCQA, 1, 32768, c), 200)]
    assert len(set(ids)) == len(ids) and min(ids) >= 0


def _the_replay_before_pr_35(params, docs_per_client):
    """``closed_loop`` and the stagger of ``_closed_loop_feeders`` as
    they stood while a client walked a finite list (PR 34), lengths
    only: per client [(prompt length, tokens asked, document, question,
    cacheable tokens)]. The oracle for what a client sees, in order."""
    docs, answers = params["doc_lengths"], params["answer_lengths"]
    per_doc, qlen = params["questions_per_doc"], params["question_len"]
    clients = []
    for c in range(params["clients"]):
        reqs = []
        for k in range(docs_per_client):
            n = docs[(c * params["doc_stride"] + k) % len(docs)]
            for q in range(per_doc):
                a = answers[(c + k * per_doc + q) % len(answers)]
                cached = q > 0 or k == 0
                reqs.append((n + qlen, a, k, q, n if cached else 0))
        clients.append(reqs[c % per_doc:])
    return clients


@CLOSED
def test_every_client_sees_the_lengths_answers_and_stagger_it_always_did(mix):
    """The first 32 requests of every client, and the cache fill, are
    the finite list's: the file's pattern, only continued."""
    old = _the_replay_before_pr_35(mix, docs_per_client=9)
    fills, replays = traffic.closed_loop_start(mix, BIG_SEED, 32768)
    assert len(fills) == len(replays) == len(old) == mix["clients"]
    for c, (fill, replay, was) in enumerate(zip(fills, replays, old)):
        got = _first(replay, 32)
        assert [(len(r["prompt"]), r["asked"], r["doc"], r["question"],
                 r["shared"]) for r in got] == was[:32]
        # the stagger: client c starts at question c mod questions_per_doc
        assert (got[0]["doc"], got[0]["question"]) == \
            (0, c % mix["questions_per_doc"])
        # the fill is the first document alone, asked for one token
        n = len(got[0]["prompt"]) - mix["question_len"]
        assert fill == {"id": -1 - c, "prompt": got[0]["prompt"][:n],
                        "asked": 1}


@CLOSED
@pytest.mark.parametrize("client", [0, 3, 7])
def test_a_clients_kth_document_is_its_own_whatever_the_others_did(
        mix, client):
    """Tokens come from [seed, client, document]: not from how many
    clients there are, nor from how far the others (or this one, in
    another run) have read."""
    alone = _first(traffic.closed_loop(mix, BIG_SEED, 32768, client), 12)
    fewer = dict(mix, clients=client + 1)
    assert _first(traffic.closed_loop(fewer, BIG_SEED, 32768, client),
                  12) == alone
    _, replays = traffic.closed_loop_start(mix, BIG_SEED, 32768)
    for c, other in enumerate(replays):         # the others read ahead
        if c != client:
            _first(other, 5 + 3 * c)
    skip = client % mix["questions_per_doc"]
    assert _first(replays[client], 12 - skip) == alone[skip:]
    # document 2 without documents 0 and 1 having been asked in full
    again = traffic.closed_loop(mix, BIG_SEED, 32768, client)
    per_doc = mix["questions_per_doc"]
    third = [r for r in _first(again, 3 * per_doc) if r["doc"] == 2]
    assert third == alone[2 * per_doc:3 * per_doc]
    other_seed = _first(traffic.closed_loop(mix, 5, 32768, client), 1)
    assert other_seed[0]["prompt"] != alone[0]["prompt"]


@CLOSED
def test_a_client_asked_four_times_todays_rate_is_still_served(mix):
    """A traced docqa run listened 88 s at 5.3 requests a second over
    sixteen clients (PERF.md, PR 34): 30 requests a client, with 29-32
    in its list. At four times that a client asks ~120; the replay has
    no end, every request fits the engine's window, and set-up builds
    nothing it does not ask."""
    _, replays = traffic.closed_loop_start(mix, 7, 32768)
    got = _first(replays[-1], 120)
    assert len(got) == 120 and len({r["id"] for r in got}) == 120
    assert max(len(r["prompt"]) + r["asked"] for r in got) \
        < mix["engine"]["max_seq_len"]
    assert got[-1]["doc"] >= 29
    assert "docs_per_client" not in mix
    assert "docs_per_client" not in mix["rehearse"]


def test_a_replay_cut_short_ends_after_its_documents_less_the_stagger():
    cut = dict(DOCQA, docs_per_client=2)
    per_doc = DOCQA["questions_per_doc"]
    _, replays = traffic.closed_loop_start(cut, 7, 32768)
    for c, replay in enumerate(replays):
        n = len(list(replay))
        assert n == 2 * per_doc - c % per_doc


def test_train_batches_are_the_same_work_every_step():
    p = {"batch": 2, "seq": 16, "distinct_batches": 3}
    a = traffic.train_batches(p, BIG_SEED, 100)
    assert len(a) == 3 and a[0]["input_ids"].shape == (2, 16)
    assert a[0]["input_ids"].max() < 100
    assert (a[0]["input_ids"] != a[1]["input_ids"]).any()
    b = traffic.train_batches(p, BIG_SEED, 100)
    assert (a[2]["input_ids"] == b[2]["input_ids"]).all()


def test_a_failed_request_counts_against_attempted():
    from benchmarks import stats
    reqs = [{"due": 1.0, "sent": 1.0, "tokens": [1.2], "asked": 1,
             "error": None},
            {"due": 2.0, "sent": 2.0, "tokens": [], "asked": 1,
             "error": "EngineDeadError()"}]
    window = stats.due_in_window(reqs, 10.0)
    assert len(window) == 2                               # attempted
    assert sum(stats.is_failed(r, 10.0) for r in window) == 1
    assert max(stats.ttfts_ms(reqs, 10.0, 10.0)) == 10000.0


@pytest.mark.parametrize("mix", [CHAT, DOCQA], ids=["chat", "docqa"])
def test_the_served_check_sends_two_prompts_with_whole_pages_in_common(mix):
    eng = mix["engine"]
    size = traffic.check_sample(eng)
    assert size["shared"] % eng["kv_block_size"] == 0
    assert eng["prefill_chunk"] < size["prompt_len"]      # two chunks
    assert 0 < size["shared"] < size["prompt_len"] - 1
    assert size["prompt_len"] + size["n_new"] < eng["max_seq_len"]
    a, b = traffic.check_requests(eng, BIG_SEED, 32768)
    assert len(a["prompt"]) == len(b["prompt"]) == size["prompt_len"]
    assert a["prompt"][:size["shared"]] == b["prompt"][:size["shared"]]
    assert a["prompt"][size["shared"]:] != b["prompt"][size["shared"]:]
    assert a["asked"] == b["asked"] == size["n_new"] and a["id"] != b["id"]
    again = traffic.check_requests(eng, BIG_SEED, 32768)
    assert [r["prompt"] for r in again] == [a["prompt"], b["prompt"]]
    other = traffic.check_requests(eng, 5, 32768)
    assert other[0]["prompt"] != a["prompt"]
