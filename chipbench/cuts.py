"""Whether a configuration file states its cut of the published config
soundly.

One chip seldom holds a published model whole.  A configuration may then
stand for one chip's share of a stated deployment (the model-configs
guide, section 4): fewer layers (the stages that would lie on further
chips), fewer experts held, a slice of the vocabulary, and the lists that
follow the layers cut with them.  A width is never cut.

A configuration file cuts a key by giving it a new value, naming it in
``reduced`` (the same list as its ``BENCHMARK.json`` entry's), giving its
published value under ``published`` and saying under ``deployment`` what
the cut stands for.  The published config is the program's registered
one, ``ARCHS[program]``.

:func:`problems` is pure: it reads plain dicts and imports nothing of the
program.
"""
from __future__ import annotations

import math
import re

#: the manifest's rule for a name, which a key in ``reduced`` is
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

#: the numeric keys a cut may change, each with the least it may be cut
#: to, from the published config: four layers past the leading dense
#: ones, eight experts (or all there are), an eighth of the vocabulary
FLOORS = {
    "n_layers": lambda pub: 4 + int(pub.get("first_dense", 0)),
    "n_experts": lambda pub: min(8, int(pub["n_experts"])),
    "vocab": lambda pub: math.ceil(int(pub["vocab"]) / 8),
}


def _plain(value):
    """A value as JSON holds it: a tuple as a list."""
    if isinstance(value, (list, tuple)):
        return [_plain(v) for v in value]
    return value


def _int(value) -> bool:
    return isinstance(value, int) and not isinstance(value, bool)


def follow_layers(value, n_published: int, n: int) -> list | None:
    """What a list that follows the layers becomes when ``n_published``
    layers are cut to ``n``.  A per-layer list (one entry a layer) keeps
    its first ``n`` entries; a list of layer indices (ascending, each
    below ``n_published``) keeps the indices below ``n``.  ``None`` for a
    list that is neither."""
    value = _plain(value)
    if len(value) == n_published:
        return value[:n]
    if (all(_int(i) for i in value) and value == sorted(set(value))
            and (not value or 0 <= value[0] and value[-1] < n_published)):
        return [i for i in value if i < n]
    return None


def uncut(file: dict) -> dict:
    """The configuration with each declared cut set back to its published
    value."""
    return dict(file, **file.get("published", {}))


def problems(published: dict, file: dict, manifest_reduced: list) -> list[str]:
    """What is unsound in the cut that configuration ``file`` states of
    the ``published`` config (model key -> registered value), with
    ``manifest_reduced`` its entry's ``reduced`` in ``BENCHMARK.json``.
    Each problem names the key at fault; an empty list means sound."""
    out = []
    reduced = list(file.get("reduced", []))
    declared = file.get("published", {})
    if reduced != list(manifest_reduced):
        out.append(f"reduced: {reduced} in the file, {list(manifest_reduced)} "
                   f"in BENCHMARK.json")
    if len(set(reduced)) != len(reduced):
        out.append(f"reduced: {reduced} names a key twice")
    if set(declared) != set(reduced):
        out.append(f"published: gives {sorted(declared)}, reduced names "
                   f"{sorted(reduced)}")
    if reduced and not str(file.get("deployment", "")).strip():
        out.append("deployment: missing; a cut configuration says what "
                   "it stands for")

    n_pub = published.get("n_layers")
    n = file.get("n_layers", n_pub)
    for k in sorted(set(published) & set(file) - set(reduced)):
        if _plain(file[k]) != _plain(published[k]):
            out.append(f"{k}: {file[k]!r} differs from the published "
                       f"{published[k]!r} and is not in reduced")
    if n != n_pub and _int(n) and _int(n_pub):
        for k in sorted(set(published) - set(reduced)):
            if not isinstance(published[k], (list, tuple)):
                continue
            want = follow_layers(published[k], n_pub, n)
            if want is not None and want != _plain(published[k]):
                out.append(f"{k}: follows the layers, cut to {n} it is "
                           f"{want}; it is not in reduced")

    for k in reduced:
        if not NAME.match(str(k)):
            out.append(f"{k!r}: not a name")
            continue
        if k not in published:
            out.append(f"{k}: not a key of the program's config")
            continue
        pub = published[k]
        if k in declared and _plain(declared[k]) != _plain(pub):
            out.append(f"{k}: published gives {declared[k]!r}, the program "
                       f"registers {pub!r}")
        if k not in file:
            out.append(f"{k}: in reduced, but the file gives no value")
            continue
        v = file[k]
        if _plain(v) == _plain(pub):
            out.append(f"{k}: in reduced, but left at the published {pub!r}")
        elif k in FLOORS:
            floor = FLOORS[k](published)
            if not _int(v):
                out.append(f"{k}: {v!r} is not a whole number")
            elif v > pub:
                out.append(f"{k}: {v} grows the published {pub}")
            elif v < floor:
                out.append(f"{k}: {v} is below its floor {floor} "
                           f"(published {pub})")
        elif isinstance(pub, (list, tuple)):
            want = (follow_layers(pub, n_pub, n)
                    if _int(n) and _int(n_pub) else None)
            if want is None:
                out.append(f"{k}: a list that does not follow the layers "
                           f"may not be cut")
            elif not isinstance(v, (list, tuple)):
                out.append(f"{k}: {v!r} is not a list")
            elif len(v) > len(pub):
                out.append(f"{k}: {len(v)} entries grow the published "
                           f"{len(pub)}")
            elif _plain(v) != want:
                out.append(f"{k}: {_plain(v)} is not the published list cut "
                           f"to {n} layers, {want}")
        else:
            out.append(f"{k}: may not be cut; only n_layers, n_experts, "
                       f"vocab and lists that follow the layers may")
    return out
