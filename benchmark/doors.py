"""The doors: every kind of thing the harness drives is a module of its own,
found by the name a data file gives it (``benchmark/README.md`` has the table).

    traffic kind   workloads/<cell>.json   "kind"             traffic/<kind>.py
    reader kind    metrics/<metric>.json   "reader"           readers/<kind>.py
    model family   configs/<config>.json   "<group>.family"   families/<family>.py
    system kind    configs/<config>.json   "system"           systems/<kind>.py
    check kind     workloads/<cell>.json   "check.kind"       checks/<kind>.py

The harness holds no list of names and no default: a name with no file is a
``MissingKind`` that names the file, which the runner's one handler turns
into a last line with ``correct`` false.
"""

from __future__ import annotations

import importlib
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


class MissingKind(Exception):
    """A data file names a kind that has no file, or names none."""


def find(door: str, name, named_by: str):
    """The module ``benchmark/<door>/<name>.py``; ``named_by`` says which
    key of which data file gave the name, for the error."""
    if not isinstance(name, str) or not name:
        raise MissingKind(f"{named_by} names nothing: it has to name a file under benchmark/{door}/")
    if not os.path.isfile(os.path.join(BENCH, door, name + ".py")):
        raise MissingKind(f"{named_by} is {name!r}, and benchmark/{door}/{name}.py does not exist")
    return importlib.import_module(f"benchmark.{door}.{name}")


def model_groups(config: dict) -> dict:
    """The configuration's model groups by name: every top-level group that
    names a ``family`` (``model`` in the two accepted files; an embedder and a
    generator side by side are two groups, each with a family of its own)."""
    return {k: g for k, g in config.items() if isinstance(g, dict) and "family" in g}


def family(group: dict, named_by: str = "a model group's `family`"):
    return find("families", group.get("family"), named_by)


def model_flops(config: dict, useful_tokens) -> float:
    """FLOPs the slice's work needs in the models, summed over the groups it
    went through.  ``useful_tokens`` is what the traffic kind's
    ``slice_readings`` kept: a dict of group name -> the family's measure of
    the work (for an encoder, each row's useful tokens), or that measure
    alone where the configuration has one group."""
    groups = model_groups(config)
    if not isinstance(useful_tokens, dict):
        if len(groups) != 1:
            raise MissingKind(
                f"the slice's useful_tokens name no model group and the configuration has {sorted(groups)}"
            )
        useful_tokens = {next(iter(groups)): useful_tokens}
    return sum(
        family(groups[g], f"configs/{config.get('name')}.json `{g}.family`").flops(groups[g], tokens)
        for g, tokens in useful_tokens.items()
    )
