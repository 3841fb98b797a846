"""How a test under ``tests/benchmarks`` holds what it guards in
``BENCHMARK.json``: by name. A later PR appends configurations, cells
and entries, and appends its cells to the ``workloads`` of accepted
entries; it may edit no test that is here. So a test finds its
configuration, its cell and its entries by their names, never by index,
place, neighbours or count; asks of a ``workloads`` list that its own
cells are in it, never that the list equals them; of an entry that its
other fields are as written; and of a cell's line that it holds
(``>=``) the names the test knows (PERF.md section 4, "how a PR adds a
cell"). ``test_benchmarks_room.py`` runs every test with ``manifest`` in
its name against a tree grown by one configuration, one cell and one
entry."""
from typing import Any, Dict, Iterable, List, Set, Tuple

from benchmarks import spec


def _one(rows: List[Dict[str, Any]], name: str) -> Dict[str, Any]:
    found = [r for r in rows if r["name"] == name]
    assert len(found) == 1, f"{name!r} is in the manifest {len(found)} times"
    return found[0]


def configuration(name: str) -> Dict[str, Any]:
    return _one(spec.benchmark()["configs"], name)


def cell(name: str) -> Dict[str, Any]:
    return _one(spec.benchmark()["workloads"], name)


def metric(name: str) -> Tuple[Dict[str, Any], List[str]]:
    """(the entry without its ``workloads``, its ``workloads``) of the
    end-to-end or per-layer metric of that name."""
    bench = spec.benchmark()
    entry = dict(_one(bench["end_to_end"] + bench["per_layer"], name))
    return entry, entry.pop("workloads", [])


def line_of(cell_name: str) -> Set[str]:
    """The names of the per-layer metrics a cell's traced line carries."""
    return {m["name"] for m in spec.load_cell(cell_name).per_layer}


def carried_only_by(names: Iterable[str], own: str) -> bool:
    """No cell but ``own`` carries any of ``names``: asked of every cell
    whose name is not ``own``, for entries that mean nothing elsewhere."""
    names = set(names)
    return not any(names & line_of(w["name"])
                   for w in spec.benchmark()["workloads"]
                   if w["name"] != own)
