"""Every one-line string of the benchmark's manifest is what the driver
accepts: 1 to 200 characters, ASCII, printable, no newline and no tab. PR
41 was refused `manifest_invalid` for one config's `why`, which
benchmarks/tests/test_manifest.py does not hold to this (it checks a
WORKLOAD's `why` and a config's `source` for length alone): the `why` and
`source` of every config and workload of BENCHMARK.json, and the same keys
of the configuration and cell files they name, one case a string so that
each counts."""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEYS = ("why", "source")


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def strings():
    """(id, text) of every such string; a key an entry lacks is no case
    (a workload has no `source`, a configuration file no `why`)."""
    man = _load("BENCHMARK.json")
    out = []
    for c in man["configs"]:
        out += [(f"config:{c['name']}:{k}", c[k]) for k in KEYS if k in c]
        data = _load(c["file"])
        out += [(f"file:{c['file']}:{k}", data[k]) for k in KEYS if k in data]
    for w in man["workloads"]:
        out += [(f"workload:{w['name']}:{k}", w[k]) for k in KEYS if k in w]
        path = os.path.join("benchmarks", "cells", w["name"] + ".json")
        cell = _load(path)
        out += [(f"file:{path}:{k}", cell[k]) for k in KEYS if k in cell]
    return out


@pytest.mark.parametrize("text", [t for _, t in strings()], ids=[i for i, _ in strings()])
def test_a_manifest_string_is_one_printable_ascii_line_of_1_to_200(text):
    assert isinstance(text, str) and 1 <= len(text) <= 200, len(text)
    assert text.isascii() and text.isprintable()  # isprintable refuses \n and \t too
    assert "\n" not in text and "\t" not in text


def test_the_cases_cover_every_config_and_workload():
    man, ids = _load("BENCHMARK.json"), [i for i, _ in strings()]
    for c in man["configs"]:
        assert f"config:{c['name']}:why" in ids and f"config:{c['name']}:source" in ids
    for w in man["workloads"]:
        assert f"workload:{w['name']}:why" in ids
