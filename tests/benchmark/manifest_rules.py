"""What a configuration's file is held to, whatever its family: to what it
declares. ``test_benchmark_manifest.py`` calls these on every
configuration of ``BENCHMARK.json``, ``test_benchmark_cut_config.py`` on
the tiny benchmark's and on files that must be refused.

A file declares, under ``published_to_sizes``, for each published key the
``sizes`` key it sets and of what kind it is. A ``width`` (hidden size,
head, feed-forward and expert widths, experts per token, window and state
sizes) or the ``positions`` is never cut. What may be cut, and is then
listed in ``reduced``, is how much of the model this chip holds: ``depth``,
``experts_held``, ``heads_held``, ``vocab_rows``, down to the floors of the
``model-configs`` guide, section 4, read from the file's own ``deployment``
and ``layer_pattern``.
"""

import re
import types

import tinybench  # noqa: F401  (puts the repo's root on sys.path)

from benchmark import harness

REDUCIBLE = {"depth", "experts_held", "heads_held", "vocab_rows"}
KINDS = REDUCIBLE | {"width", "positions"}
# a second guard beside the declared kind: a key called like a width is not
# cut, whatever kind its file gives it
WIDTH_NAME = re.compile(r"(_dim|_rank|_width|_factor|_window|per_tok|"
                        r"per_token|(?<!vocab)_size)$|^(n_embd|n_inner|"
                        r"d_model|d_ff|d_kv)$")
LAYERS_AFTER_DENSE = 4      # at least, and a whole period
EXPERTS_HELD = 8            # at least, in a layer that has experts
VOCAB_SHARE = 8             # at least one part in this many


class Refused(AssertionError):
    """A configuration's file breaks a rule; the message names it."""


def hold(condition, reason: str) -> None:
    if not condition:
        raise Refused(reason)


def config_file(doc: dict, entry: dict, dirs) -> None:
    """The file against its manifest entry, and within itself: the sizes
    the program is built with are the sizes the reference and the counts
    are given, and each count it names is a function of the benchmark."""
    hold(doc["reduced"] == entry["reduced"],
         f"reduced in the manifest {entry['reduced']} is unlike the "
         f"file's {doc['reduced']}")
    kw, sizes = doc["program"]["config_kwargs"], doc["sizes"]
    for key in sorted(kw.keys() & sizes.keys()):
        hold(kw[key] == sizes[key],
             f"config_kwargs[{key!r}] is {kw[key]}, sizes[{key!r}] "
             f"{sizes[key]}")
    for key in ("vocab_size", "max_seq"):       # the harness reads them
        hold(key in sizes, f"sizes lacks {key!r}")
    cell = types.SimpleNamespace(config=doc, dirs=list(dirs))
    for key in ("flops_rule", "kernel_counts"):
        try:
            harness.named_count(cell, key)
        except ValueError as e:
            raise Refused(str(e)) from e
    for limit in ("loss_rel", "trainer_vs_plain_loss_rel"):
        hold(doc["limits"][limit] > 0, f"limits[{limit!r}] is not above 0")


def published_sizes(doc: dict) -> None:
    """Every published key runs at its published value unless it is in
    ``reduced``; a key in ``reduced`` runs at less, is of a kind that may
    be cut, and keeps to the floors."""
    pub, sizes, reduced = doc["published"], doc["sizes"], doc["reduced"]
    mapping = doc["published_to_sizes"]
    for key in reduced:
        hold(key in mapping, f"{key!r} is in reduced and not in "
             f"published_to_sizes")
    for key, how in mapping.items():
        hold(how["kind"] in KINDS, f"{key!r}: no kind {how['kind']!r}")
        run, published = sizes[how["sizes"]], pub[key]
        if key in doc:      # the file's own copy of the key, as it is run
            hold(doc[key] == run, f"{key!r} is {doc[key]} at the file's top "
                 f"level and runs at {run}")
        if key not in reduced:
            hold(run == published, f"{key!r} runs at {run}, is published "
                 f"as {published} and is not in reduced")
            continue
        hold(how["kind"] in REDUCIBLE and not WIDTH_NAME.search(key),
             f"{key!r} ({how['kind']}) is in reduced: no width and no "
             f"positions are ever cut")
        hold(run < published, f"{key!r} is in reduced and runs at its "
             f"published value {published}")
    if reduced:
        cut_floors(doc)


def cut_floors(doc: dict) -> None:
    """The floors of a cut configuration, by kind, from the file's own
    numbers."""
    pub, sizes = doc["published"], doc["sizes"]
    deployment = doc.get("deployment") or {}
    chips = deployment.get("chips_sharing_a_layer")
    hold(isinstance(chips, int) and chips >= 1 and deployment.get(
        "held_here"), "a cut configuration states its deployment: "
        "chips_sharing_a_layer, and what this chip holds of a layer "
        "(held_here)")
    for key in doc["reduced"]:
        how = doc["published_to_sizes"][key]
        run, published = sizes[how["sizes"]], pub[key]
        if how["kind"] == "depth":
            depth_floor(doc, run, published)
        elif how["kind"] == "experts_held":
            hold(run * chips == published, f"{key!r}: {run} experts held "
                 f"are not {published} over {chips} chips")
            hold(run >= EXPERTS_HELD, f"{key!r}: {run} experts held, at "
                 f"least {EXPERTS_HELD} are")
        elif how["kind"] == "heads_held":
            hold(published % run == 0 and published // run <= chips,
                 f"{key!r}: {run} heads held are no whole share of "
                 f"{published} over at most {chips} chips")
        elif how["kind"] == "vocab_rows":
            hold(run * VOCAB_SHARE >= published, f"{key!r}: {run} rows are "
                 f"under one part in {VOCAB_SHARE} of {published}")


def depth_floor(doc: dict, run: int, published: int) -> None:
    """``layer_pattern`` (where layers are of several kinds) gives the
    ``published`` list of kinds, its ``period``, how many ``leading_dense``
    layers come before the periodic part, and the list that is ``run``:
    leading dense layers count once or more, and what follows them is
    whole periods of the published pattern, four layers at the least."""
    pattern = doc.get("layer_pattern")
    if pattern is None:
        hold(run >= LAYERS_AFTER_DENSE, f"{run} layers run, at least "
             f"{LAYERS_AFTER_DENSE} are")
        return
    kinds, period = pattern["published"], pattern["period"]
    dense, ran = pattern["leading_dense"], pattern["run"]
    hold(len(kinds) == published and len(ran) == run,
         f"layer_pattern lists {len(kinds)} published and {len(ran)} run "
         f"layers, the sizes say {published} and {run}")
    one = kinds[dense:dense + period]
    hold(len(one) == period and kinds[dense:] == (
        one * len(kinds))[:len(kinds) - dense],
        f"the published layers after the first {dense} do not repeat with "
        f"period {period}")
    kept = 0
    while kept < min(dense, len(ran)) and ran[kept] == kinds[kept]:
        kept += 1
    hold(kept >= min(dense, 1), "the leading dense layers count once: at "
         "least one of them is run")
    rest = ran[kept:]
    hold(len(rest) >= max(LAYERS_AFTER_DENSE, period)
         and rest == one * (len(rest) // period),
         f"{len(rest)} layers after the dense ones: at least "
         f"{LAYERS_AFTER_DENSE}, in whole periods of {period}, are run")
