"""The rules every configuration keeps, whatever its model: what ``BENCHMARK.json`` says
of it agrees with its file, what was cut is listed with what was published, the model's
family is there, and the cut stays inside the ``model-configs`` guide's floors. The rules
follow the configuration's own keys; only ``PINNED`` knows a model, by its source.
"""

from __future__ import annotations

import json
import os
import re

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


def entry_problems(bench: dict, entry: dict, root: str) -> list:
    """What one ``configs`` entry of ``bench`` (read from ``root``) breaks; [] if nothing."""
    out = []

    def need(ok, what):
        if not ok:
            out.append(f"{entry.get('name')}: {what}")

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


def config_problems(root: str) -> list:
    """Every broken rule of every configuration of ``<root>/BENCHMARK.json``, one line each."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [p for entry in bench["configs"] for p in entry_problems(bench, entry, root)]
