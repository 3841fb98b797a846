"""The one traffic generator. A mix is a data file under ``traffic/``
(``kind`` plus parameters); this module turns it and ``--seed`` into
the inputs of a run. The amount, the sizes and the schedule of the work
are fixed by the file (its own ``schedule_seed`` draws the order inside
each block of arrivals and the instant inside each arrival interval);
``--seed`` draws the token ids, nothing else: a tail over some tens of
requests does not repeat when the seed also moves who queues behind
whom (PERF.md, PR 24). No JAX."""
import itertools
import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np


def lognormal_quantiles(spec: Dict[str, Any], n: int) -> List[int]:
    """The n mid-point quantiles of a clipped log-normal, ascending:
    a fixed multiset, no sampling."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    nd = NormalDist()
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(spec["max"], max(spec["min"], round(x)))))
    return out


def length_pool(params: Dict[str, Any]) -> List[List[tuple]]:
    """strata x members of (prompt_len, answer_len): prompts are the
    quantiles in ascending order cut into ``strata`` equal groups,
    answers the quantiles of their own law paired once with the seed
    written in the file."""
    strata, per = params["strata"], params["members_per_stratum"]
    n = strata * per
    prompts = lognormal_quantiles(params["prompt"], n)
    answers = lognormal_quantiles(params["answer"], n)
    order = np.random.default_rng(params["pair_seed"]).permutation(n)
    pairs = [(prompts[i], answers[int(order[i])]) for i in range(n)]
    return [pairs[s * per:(s + 1) * per] for s in range(strata)]


def _tokens(rng, vocab: int, n: int) -> List[int]:
    return rng.integers(2, vocab, size=n).tolist()


def warm_seconds(params: Dict[str, Any]) -> float:
    """Length of the ``warm_blocks`` whole blocks of arrivals that run
    before an open loop's window opens, so that it opens on a queue in
    its steady state and on a block's boundary."""
    return params.get("warm_blocks", 0) * params["lengths"]["strata"] \
        / float(params["rate_rps"])


def open_loop(params: Dict[str, Any], seed: int, seconds: float,
              vocab: int) -> List[Dict[str, Any]]:
    """Requests due in [-warm, seconds), in due order; the window is
    [0, seconds). One arrival in each interval of 1/rate, at an instant
    the file's ``schedule_seed`` draws; every ``strata`` consecutive
    arrivals hold one member of each stratum of prompt length (block b
    takes member b of each), in an order that seed draws too. ``seed``
    draws the token ids."""
    rng = np.random.default_rng(seed)
    sched = np.random.default_rng(params["schedule_seed"])
    pool = length_pool(params["lengths"])
    strata, rate = len(pool), float(params["rate_rps"])
    warm = params.get("warm_blocks", 0)
    n_blocks = warm + math.ceil(seconds * rate / strata)
    out = []
    for b in range(n_blocks):
        members = [pool[s][b % len(pool[s])] for s in range(strata)]
        for j, s in enumerate(sched.permutation(strata)):
            i = (b - warm) * strata + j
            due = (i + float(sched.random())) / rate
            plen, alen = members[int(s)]
            prompt = _tokens(rng, vocab, plen)     # drawn even if unsent
            if due < seconds:
                out.append({"id": i, "due": due, "prompt": prompt,
                            "asked": alen, "stratum": int(s)})
    return out


def check_sample(engine: Dict[str, Any]) -> Dict[str, int]:
    """Sizes of the correctness check's sequences, from the engine's
    own chunk and page: a prompt of a chunk and a half and three tokens
    (so a whole chunk, a part of one and a ragged page), ``n_new``
    tokens decoded after it, and the whole pages of it (``shared``)
    that a second prompt repeats, for the prefix cache to serve."""
    n_new = 8
    prompt_len = min(engine["max_seq_len"] - n_new - 1,
                     engine["prefill_chunk"] * 3 // 2 + 3)
    bs = engine["kv_block_size"]
    return {"prompt_len": prompt_len, "n_new": n_new,
            "shared": prompt_len * 5 // 6 // bs * bs}


def check_requests(engine: Dict[str, Any], seed: int, vocab: int
                   ) -> List[Dict[str, Any]]:
    """Two requests for the engine itself to serve in set-up: the
    second repeats the first's leading ``shared`` tokens under another
    ending, so the engine answers it out of its prefix cache."""
    size = check_sample(engine)
    rng = np.random.default_rng([int(seed), 1])
    head = _tokens(rng, vocab, size["shared"])
    return [{"id": -100 - i, "asked": size["n_new"], "keep_ids": True,
             "prompt": head + _tokens(rng, vocab,
                                      size["prompt_len"] - size["shared"])}
            for i in range(2)]


#: a client's request ids start here times its number
CLIENT_ID_STRIDE = 10**6


def closed_loop(params: Dict[str, Any], seed: int, vocab: int,
                client: int) -> Iterator[Dict[str, Any]]:
    """Client c's requests in order, without an end: document k = 0, 1,
    2, ... has the length the file gives it (its ``doc_lengths``,
    ``answer_lengths`` and ``doc_stride`` continued modulo their
    lengths) and is asked ``questions_per_doc`` times in a row with a
    fresh question. A document is built when the client comes to it, so
    a run pays for the documents it asks and for no other, and a faster
    engine is asked more of them, never fewer clients. The seed draws
    the tokens only, document by document (``[seed, c, k]``): client c's
    k-th document is the same whatever the other clients have read and
    however many there are. ``docs_per_client``, which no cell's file
    states, ends the replay after that many documents (the tests' way to
    the guard that a client ran out). ``shared`` is how many of the
    prompt's tokens the prefix cache can serve: the document, once a
    question (or set-up's fill, for the first) has prefilled it."""
    docs, answers = params["doc_lengths"], params["answer_lengths"]
    per_doc, qlen = params["questions_per_doc"], params["question_len"]
    last = params.get("docs_per_client")
    for k in itertools.count() if last is None else range(last):
        rng = np.random.default_rng([int(seed), client, k])
        n = docs[(client * params["doc_stride"] + k) % len(docs)]
        doc = _tokens(rng, vocab, n)
        for q in range(per_doc):
            a = answers[(client + k * per_doc + q) % len(answers)]
            yield {"id": k * per_doc + q + CLIENT_ID_STRIDE * client,
                   "prompt": doc + _tokens(rng, vocab, qlen), "asked": a,
                   "doc": k, "question": q,
                   "shared": n if q > 0 or k == 0 else 0}


def closed_loop_start(params: Dict[str, Any], seed: int, vocab: int
                      ) -> Tuple[List[Dict[str, Any]], List[Iterator]]:
    """(fills, replays). Every client's first document is prefilled
    once in set-up (``fills``: the document alone, one token asked), and
    client c's replay starts at question c mod ``questions_per_doc`` of
    it, so that the window opens on clients spread over their documents
    as in a long-running service, not on all of them cold at once."""
    per_doc, qlen = params["questions_per_doc"], params["question_len"]
    fills, replays = [], []
    for c in range(params["clients"]):
        reqs = closed_loop(params, seed, vocab, c)
        first = next(reqs)
        fills.append({"id": -1 - c, "prompt": first["prompt"][:-qlen],
                      "asked": 1})
        replays.append(itertools.islice(
            itertools.chain([first], reqs), c % per_doc, None))
    return fills, replays


def train_batches(params: Dict[str, Any], seed: int, vocab: int
                  ) -> List[Dict[str, np.ndarray]]:
    """``distinct_batches`` packed batches of ``batch`` x ``seq`` token
    ids, cycled by the cell: every step does the same amount of work."""
    rng = np.random.default_rng(seed)
    shape = (params["batch"], params["seq"])
    return [{"input_ids": rng.integers(0, vocab, size=shape)
             .astype(np.int32),
             "loss_mask": np.ones(shape, np.float32)}
            for _ in range(params["distinct_batches"])]
