"""The rules every configuration keeps, whatever its model: what ``BENCHMARK.json`` says
of it agrees with its file, what was cut is listed with what was published, the model's
family is there, and the cut stays inside the ``model-configs`` guide's floors. The rules
follow the configuration's own keys; only ``PINNED`` knows a model, by its source. And what
a cell's entries say of it and what the whole-window entries list, each found by its name
(``cell_problems``, ``window_entry_problems``).
"""

from __future__ import annotations

import json
import os
import re

from .run import HERE, applies
from .traffic import load as load_mix

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# the catalog's spellings of "routed experts a layer" and "leading dense layers"
EXPERTS = ("n_routed_experts", "num_experts", "num_local_experts", "moe_num_experts")
LEADING_DENSE = ("first_k_dense_replace", "num_dense_layers")
MIN_EXPERTS, MIN_VOCAB_SHARE, MIN_LAYERS_AFTER_DENSE = 8, 1 / 8, 4

# A source's published sizes, held against every file that names it: ``file`` keys stand in
# the file as they are, ``published`` is the whole of the file's ``published`` group.
PINNED = {
    "https://huggingface.co/mistralai/Mistral-7B-v0.1/blob/main/config.json": {
        "file": {"hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32000,
                 "sliding_window": 4096, "rope_theta": 10000.0, "rms_norm_eps": 1e-05},
        "published": {"num_hidden_layers": 32}},
}


def _number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def _checker(out: list, prefix: str):
    def need(ok, what):
        if not ok:
            out.append(f"{prefix}{what}")
    return need


def entry_problems(bench: dict, entry: dict, root: str) -> list:
    """What one ``configs`` entry of ``bench`` (read from ``root``) breaks; [] if nothing."""
    out = []
    need = _checker(out, f"{entry.get('name')}: ")
    need(NAME.match(entry["name"]), "its name is not a name")
    need(any(entry["file"].startswith(p + "/") for p in bench["paths"]),
         f"its file {entry['file']} lies under none of paths {bench['paths']}")
    path = os.path.join(root, entry["file"])
    if not os.path.exists(path):
        return out + [f"{entry['name']}: no file {path}"]
    with open(path) as f:
        c = json.load(f)
    reduced, published = c.get("reduced", []), c.get("published", {})
    need(entry["reduced"] == reduced,
         f"reduced {entry['reduced']} in the entry, {reduced} in the file")
    need(reduced, "reduced is empty: no published model fits a chip uncut")
    need(all(k in c for k in reduced), f"a key of reduced {reduced} is not in the file")
    need(sorted(published) == sorted(reduced), f"published {sorted(published)} does not give "
         f"exactly the keys of reduced {sorted(reduced)}")
    for k in set(reduced) & set(published) & set(c):
        if _number(published[k]) and _number(c[k]):
            need(c[k] < published[k], f"{k} {c[k]} is no cut of the published {published[k]}")
    need(entry["source"] == c.get("source"), "source differs between the entry and the file")
    need(len(entry["source"]) <= 200, "source is longer than 200 characters")
    need(c.get("limits") and all(v is not None for v in c["limits"].values()),
         "limits is empty or holds a null")
    family = os.path.join(os.path.dirname(os.path.dirname(path)), "families",
                          f"{c.get('model_type')}.py")
    need(os.path.exists(family), f"model_type {c.get('model_type')!r} has no family file {family}")
    # the guide's floors, where the configuration has the keys
    for k in EXPERTS:
        if k in c:
            need(c[k] >= MIN_EXPERTS, f"{k} {c[k]}: fewer than {MIN_EXPERTS} routed experts held")
    if "vocab_size" in published and "vocab_size" in c:
        need(c["vocab_size"] >= MIN_VOCAB_SHARE * published["vocab_size"],
             f"vocab_size {c['vocab_size']} is under an eighth of {published['vocab_size']}")
    for k in LEADING_DENSE:
        if k in c:
            after = c["num_hidden_layers"] - c[k]
            need(after >= MIN_LAYERS_AFTER_DENSE,
                 f"{after} layers follow the {c[k]} leading dense ones: fewer than "
                 f"{MIN_LAYERS_AFTER_DENSE}")
    pinned = PINNED.get(entry["source"])
    if pinned:
        held = {k: c.get(k) for k in pinned["file"]}
        need(held == pinned["file"], f"published sizes changed: {held}")
        need(published == pinned["published"],
             f"published {published} is not {pinned['published']}")
    return out


def cell_problems(bench: dict, cell: str, config: str, traffic: str, chips: int, own: set,
                  reports: set) -> list:
    """What ``bench``'s entries break of one cell, found by name and never by position, so
    that a cell, a configuration or a metric appended after it changes nothing here; [] if
    nothing. ``own`` names the per-layer entries the cell was accepted with for itself: each
    is there once and lists the cell, whatever cells a later change appends to its list.
    ``reports`` names the end-to-end metrics the cell reports."""
    out = []
    need = _checker(out, f"{cell}: ")
    found = [w for w in bench["workloads"] if w["name"] == cell]
    need(len(found) == 1, f"{len(found)} entries under workloads")
    for w in found:
        got = (w["config"], w["traffic"], w["chips"])
        need(got == (config, traffic, chips),
             f"config, traffic and chips are {got}, not {(config, traffic, chips)}")
    n = sum(c["name"] == config for c in bench["configs"])
    need(n == 1, f"its config {config} has {n} entries under configs")
    e2e = {m["name"] for m in bench["end_to_end"] if applies(m, {"name": cell})}
    need(e2e == set(reports), f"reports {sorted(e2e)}, not {sorted(reports)}")
    for name in sorted(own):
        lists = [cell in m.get("workloads", ()) for m in bench["per_layer"] if m["name"] == name]
        need(lists == [True], f"its own per-layer entry {name}: {len(lists)} entries, "
             f"{sum(lists)} of them listing it")
    unnamed = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    need(not unnamed, f"per-layer entries {unnamed} apply to it without naming it")
    return out


# PR 36's readers of the program's phase ledger over the whole window, and the catalog's
# spellings of a learned sparse selection
WINDOW = ("engine_admit_share_window.latency", "engine_admit_share_window.throughput",
          "engine_host_ms_per_step_window.latency", "engine_host_ms_per_step_window.throughput",
          "decode_gap_ms_p99.latency", "decode_gap_ms_p99.throughput",
          "prefill_ms_per_chunk.throughput", "moe_pairs_per_token_window.throughput",
          "moe_max_over_mean_load_window.throughput", "dsa_selected_share_window.throughput")
INDEXER = ("index_topk", "sa_config")


def window_entry_problems(bench: dict, root: str) -> list:
    """What ``bench`` (its files read from ``root``) breaks of the ``WINDOW`` entries, each
    found by its name; [] if nothing. An entry lists exactly the cells that report the
    end-to-end metric it moves and hold what it reads: the ``moe`` ones the cells whose
    configuration holds routed experts, ``dsa_selected_share_window`` those whose
    configuration has an indexer, ``prefill_ms_per_chunk`` those whose traffic is a backlog."""
    out = []
    need = _checker(out, "")
    holds = {}
    for w in bench["workloads"]:
        file = next(c["file"] for c in bench["configs"] if c["name"] == w["config"])
        with open(os.path.join(root, file)) as f:
            c = json.load(f)
        kind = load_mix("traffic", w["traffic"])["kind"]
        holds[w["name"]] = {"moe": any(k in c for k in EXPERTS),
                            "dsa": any(k in c for k in INDEXER), "prefill": kind == "serve_backlog"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in WINDOW}
    for name in WINDOW:
        found = [m for m in bench["per_layer"] if m["name"] == name]
        need(len(found) == 1, f"{name}: {len(found)} entries under per_layer")
        for m in found:
            need(set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"},
                 f"{name}: keys {sorted(m)}")
            need(NAME.match(name) and m["better"] == "lower" and m["layer"] in layers,
                 f"{name}: name, better or layer")
            stem, reader = name.split("_")[0], name.split(".")[0]
            need(m["source"] == ("program_counter" if stem in ("moe", "dsa") else "host_clock"),
                 f"{name}: source {m['source']}")
            need(reader.endswith("_window") or reader in ("decode_gap_ms_p99", "prefill_ms_per_chunk"),
                 f"{name}: not a reader of the whole window")
            need(os.path.exists(os.path.join(HERE, "metrics", f"{reader}.py")),
                 f"{name}: no reader")
            want = {w["name"] for w in bench["workloads"]
                    if applies(e2e[m["moves"]], w) and holds[w["name"]].get(stem, True)}
            listed = m["workloads"]
            need(len(set(listed)) == len(listed) and set(listed) == want,
                 f"{name}: lists {listed}, not the cells {sorted(want)}")
    return out


def config_problems(root: str) -> list:
    """Every broken rule of every configuration of ``<root>/BENCHMARK.json``, one line each."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [p for entry in bench["configs"] for p in entry_problems(bench, entry, root)]
