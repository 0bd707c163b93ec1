"""The check of a configuration's cut (``chipbench/cuts.py``), on cuts of
olmo-1b's file made in memory and on a published config with lists that
follow the layers."""
import os

import pytest

from chipbench import cuts, harness
from repro.configs import ARCHS

OLMO = harness.load_json(os.path.join(harness.ROOT,
                                      "chipbench/configs/olmo-1b.json"))


def _cut(values: dict, declare=(), published=None, manifest=None):
    """olmo-1b's file with ``values`` set, ``declare`` in ``reduced`` and
    ``published`` (by default the registered values of ``declare``);
    with the manifest's ``reduced`` (by default the file's)."""
    file = dict(OLMO, **values, reduced=list(declare))
    pub = harness.published_config(OLMO["program"])
    if declare:
        file["published"] = (published if published is not None
                             else {k: pub[k] for k in declare})
    return file, list(declare) if manifest is None else manifest


# (file, manifest's reduced, key the problem names or None when sound)
OLMO_CUTS = {
    "n_layers_16_to_8": (*_cut({"n_layers": 8}, ["n_layers"]), None),
    "vocab_to_an_eighth": (*_cut({"vocab": 6288}, ["vocab"]), None),
    "undeclared_cut": (*_cut({"n_layers": 8}), "n_layers"),
    "declared_but_unchanged": (*_cut({}, ["n_layers"]), "n_layers"),
    "width_cut": (*_cut({"d_model": 1024}, ["d_model"]), "d_model"),
    "three_layers": (*_cut({"n_layers": 3}, ["n_layers"]), "n_layers"),
    "vocab_below_an_eighth": (*_cut({"vocab": 6287}, ["vocab"]), "vocab"),
    "published_unlike_the_program": (
        *_cut({"n_layers": 8}, ["n_layers"], {"n_layers": 24}), "n_layers"),
    "manifest_unlike_the_file": (
        *_cut({"n_layers": 8}, ["n_layers"], manifest=[]), "reduced"),
    "cut_that_grows": (*_cut({"n_layers": 20}, ["n_layers"]), "n_layers"),
}


@pytest.mark.parametrize("case", sorted(OLMO_CUTS))
def test_cuts_of_olmo(case):
    """A sound cut reads no problem, and set back to its published values
    it is the program's registered config again; each unsound one reads a
    problem that names the key at fault."""
    file, manifest, fault = OLMO_CUTS[case]
    got = cuts.problems(harness.published_config(file["program"]), file,
                        manifest)
    if fault is None:
        assert got == []
        assert (harness.program_config(cuts.uncut(file))
                == ARCHS[file["program"]])
    else:
        assert any(p.startswith(fault + ":") for p in got), got


def test_an_undeclared_cut_is_not_the_published_config():
    """What ``test_published_sizes_are_the_programs`` asserts fails on a
    file that cuts a key without declaring it."""
    file, _, _ = OLMO_CUTS["undeclared_cut"]
    assert (harness.program_config(cuts.uncut(file))
            != ARCHS[file["program"]])


def test_a_depth_cut_of_olmo_runs_at_its_cut_and_matches_the_reference():
    file, _, _ = OLMO_CUTS["n_layers_16_to_8"]
    cfg = harness.program_config(file)
    assert cfg.n_layers == 8 and cfg.d_model == ARCHS["olmo-1b"].d_model
    harness.check_table(cfg, harness.reference_module(file).param_table(file))


# a published config with a list of layer indices and a per-layer list, as
# Zamba2-7B's hybrid_layer_ids and layers_block_type
LAYERED = {"n_layers": 12, "d_model": 64, "vocab": 1000,
           "hybrid_layer_ids": (2, 5, 8, 11),
           "block_type": tuple("mmhmmhmmhmmh")}
CUT6 = {"n_layers": 6, "hybrid_layer_ids": [2, 5],
        "block_type": list("mmhmmh")}
LIST_CUTS = {
    "as_specified": (CUT6, None),
    "index_list_kept_whole": (dict(CUT6, hybrid_layer_ids=[2, 5, 8, 11]),
                              "hybrid_layer_ids"),
    "index_list_cut_wrong": (dict(CUT6, hybrid_layer_ids=[2, 8]),
                             "hybrid_layer_ids"),
    "per_layer_list_last_entries": (
        dict(CUT6, block_type=list("mhmmhm")), "block_type"),
    "per_layer_list_grows": (dict(CUT6, block_type=list("mmhmmhmmhmmhm")),
                             "block_type"),
    "lists_cut_layers_not": (dict(CUT6, n_layers=12), "hybrid_layer_ids"),
    "layers_cut_lists_not_declared": (
        {"n_layers": 6}, "hybrid_layer_ids"),
}


@pytest.mark.parametrize("case", sorted(LIST_CUTS))
def test_lists_that_follow_the_layers(case):
    values, fault = LIST_CUTS[case]
    reduced = sorted(values)
    file = dict(LAYERED, **values, reduced=reduced,
                published={k: LAYERED[k] for k in reduced},
                deployment="layers 0-5 of 12: the first stage of two chips")
    got = cuts.problems(LAYERED, file, reduced)
    if fault is None:
        assert got == []
    else:
        assert any(p.startswith(fault + ":") for p in got), got


def test_zamba2_7b_hybrid_layer_ids_cut_to_its_first_stage():
    ids = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)
    assert cuts.follow_layers(ids, 81, 24) == [6, 11, 17, 23]
