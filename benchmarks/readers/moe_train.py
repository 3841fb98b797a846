"""What a TRAINED configuration of routed experts and window layers adds
to read (``roofline_moe_train.py`` has the counting): the share of the
whole step's peak with the routed experts counted by what a token MEETS
and the window layers by their keys (``mfu_routed``, host clock), and
from the reduced trace the experts' and the windowed flash kernels' shares
of their rooflines and the expert layers' share of device time.

``train_cell.py`` puts no model group and no program counter into
``obs``, so the configuration's sizes come from its own file, named in the
metric's ``args`` (``config``), with the depth and the sequence from
``obs["train"]``. None where there is nothing to read: no training
window, no trace, a rehearsal's CPU run, a program without the scopes or
the kernels (the parent of the PR that added them)."""
import functools
import json
import os

from benchmarks import roofline, roofline_moe_train as R, stats, trace as T
from benchmarks.spec import HERE

#: the named scopes the program puts its expert layer's ops under; XLA's
#: grouped-product kernels keep no scope path and are found by op name
MOE_SCOPE, EXPERTS_SCOPE = "layer/mlp/moe", "layer/mlp/moe/moe_experts"


@functools.lru_cache(maxsize=None)
def _program(config: str):
    with open(os.path.join(HERE, "configs", config + ".json")) as f:
        return json.load(f)["program"]


def _sizes(obs, config: str):
    return dict(_program(config), n_layers=obs["train"]["n_layers"])


def _grouped_unscoped(tr, under: str) -> float:
    """Seconds a chip in grouped products that carry no scope below
    ``under`` (those that do are in ``by_scope`` already)."""
    return sum(op["seconds"] for op in tr["op_calls"].values()
               if op["kind"].startswith("ragged-dot")
               and not (op["scope"] + "/").startswith(under + "/")) \
        / tr["chips"]


def read(obs, what, config):
    train = obs.get("train")
    if not train or obs["device"]["platform"] != "tpu":
        return None
    sizes = _sizes(obs, config)
    kind = obs["device"]["kind"]
    if what == "mfu_routed":
        rate = stats.window_tokens_per_s(train["step_ends"],
                                         train["tokens_per_step"])
        per_token = R.train_flops_per_token(sizes, train["seq"])
        return 100.0 * rate * per_token / (
            train["chips"] * roofline.peaks(kind)["bf16_flops"])
    tr = obs.get("trace")
    if not tr:
        return None
    scopes = tr.get("by_scope", {})
    if what == "moe_share":
        if MOE_SCOPE not in scopes:
            return None
        return 100.0 * (scopes[MOE_SCOPE]
                        + _grouped_unscoped(tr, MOE_SCOPE)) / tr["busy_s"]
    if what == "experts_roofline":
        if EXPERTS_SCOPE not in scopes:
            return None
        spent = scopes[EXPERTS_SCOPE] + _grouped_unscoped(tr, EXPERTS_SCOPE)
        # the traced steps: tokens through every expert layer
        steps = _traced_steps(tr)
        if not spent or not steps:
            return None
        rows = R.assignments_here(train["tokens_per_step"]
                                  / train["chips"], sizes)
        flops, nbytes = R.expert_products(rows, sizes)
        least = steps * sizes["n_layers"] * roofline.min_seconds(
            flops, nbytes, kind)
        return 100.0 * least / spent
    if what == "flash_window_roofline":
        least = spent = 0.0
        for op in tr["op_calls"].values():
            if op["kind"] in R.WINDOW_KINDS:
                flops, nbytes = R.flash_window_call(
                    op["kind"], T.result_shape(op["name"])[1],
                    sizes["sliding_window"])
                least += op["calls"] * roofline.min_seconds(
                    flops, nbytes, kind)
                spent += op["seconds"]
        return 100.0 * least / spent if spent else None
    raise ValueError(f"unknown quantity {what!r}")


def _traced_steps(tr) -> float:
    """Steps inside the traced stretch (the cell's ``trace_steps`` is not
    in ``obs``): an op under the scope ``optimizer`` runs once a step, so
    the median of those ops' calls (one clipped at the stretch's edge
    does not move a median)."""
    calls = sorted(op["calls"] for op in tr["op_calls"].values()
                   if (op["scope"] + "/").startswith("optimizer/"))
    return calls[len(calls) // 2] / tr["chips"] if calls else 0.0
