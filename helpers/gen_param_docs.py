#!/usr/bin/env python
"""Generate docs/Parameters.md from the Config dataclass + alias table.

Counterpart of the reference's helpers/parameter_generator.py, which parses
config.h comment blocks into docs/Parameters.rst and config_auto.cpp and whose
output CI diffs to keep code and docs in lockstep
(/root/reference/.ci/test.sh:27-60). Here the single source of truth is
lightgbm_tpu/config.py itself: the dataclass fields (name, type, default,
section) and PARAM_ALIASES are introspected, so the doc can never drift from
the code without tests/test_param_docs.py noticing.

Usage:  python helpers/gen_param_docs.py [--check]
  --check: exit 1 if docs/Parameters.md is out of date (the CI mode).
"""
from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OUT = os.path.join(REPO, "docs", "Parameters.md")

#: what this port does with a parameter where the name and the default do not
#: say it: one indented line under the parameter's entry
NOTES = {
    "feature_fraction": (
        "under 1.0 each tree draws `max(1, int(feature_fraction x F))` of the table's `F` "
        "columns (one host stream, `feature_fraction_seed`) and the serial grower is handed "
        "those columns alone: histograms, carries and split scan run at the drawn width, so a "
        "tree costs about the drawn share of the columns, and `Booster.feature_draws()` is the "
        "record of the draws. The draw stays a mask over all `F` columns (the same trees at "
        "the full cost) where state lives in the table's column space: EFB-bundled datasets "
        "(group-space histograms), `tree_learner` `feature`, `voting` and `data` (a sharded "
        "matrix), forced splits, CEGB, `device_chunk_size > 1` (masks pre-drawn inside the "
        "fused scan) and the native host learner (`device_type=cpu`)"
    ),
}


def _sections():
    """Parse config.py's `# --- section ---` groupings in declaration order."""
    import dataclasses

    from lightgbm_tpu.config import Config

    src = open(os.path.join(REPO, "lightgbm_tpu", "config.py")).read()
    body = src.split("class Config:", 1)[1]
    section = "core"
    field_section = {}
    for line in body.splitlines():
        m = re.match(r"\s*# --- (.+?) ---", line)
        if m:
            section = m.group(1)
            continue
        m = re.match(r"\s{4}(\w+)\s*:", line)
        if m:
            field_section[m.group(1)] = section
        if line.strip().startswith("def "):
            break

    fields = []
    for f in dataclasses.fields(Config):
        default = (
            f.default
            if f.default is not dataclasses.MISSING
            else f.default_factory()
        )
        type_name = {
            "str": "string", "int": "int", "float": "double", "bool": "bool",
        }.get(getattr(f.type, "__name__", str(f.type)), None)
        if type_name is None:
            t = str(f.type)
            type_name = "multi-double" if "float" in t else (
                "multi-int" if "int" in t else "multi-string"
            )
        fields.append(
            (field_section.get(f.name, "core"), f.name, type_name, default)
        )
    return fields


def render() -> str:
    from lightgbm_tpu.config import PARAM_ALIASES

    fields = _sections()
    alias_of = {}
    for alias, canonical in sorted(PARAM_ALIASES.items()):
        alias_of.setdefault(canonical, []).append(alias)

    lines = [
        "# Parameters",
        "",
        "All training/prediction parameters of lightgbm_tpu, generated from",
        "`lightgbm_tpu/config.py` by `helpers/gen_param_docs.py` — do not edit",
        "by hand; regenerate with `python helpers/gen_param_docs.py`.",
        "",
        "Names, defaults, and aliases follow the reference's parameter table",
        "(`docs/Parameters.rst`, generated from `config.h` comments by",
        "`helpers/parameter_generator.py`). Parameters are passed as",
        "`key=value` pairs on the CLI / config file, or as dict entries in",
        "the Python `params` argument; aliases resolve to the canonical name",
        "with conflict detection (`config.py Config.canonicalize`).",
        "",
    ]
    current = None
    for section, name, type_name, default in fields:
        if section != current:
            lines += ["## %s" % section.capitalize(), ""]
            current = section
        if isinstance(default, str):
            default_txt = '"%s"' % default
        elif isinstance(default, bool):
            default_txt = "true" if default else "false"
        elif isinstance(default, list):
            default_txt = "(empty)" if not default else ",".join(map(str, default))
        else:
            default_txt = str(default)
        entry = "- **`%s`** : %s, default = `%s`" % (name, type_name, default_txt)
        aliases = alias_of.get(name)
        if aliases:
            entry += ", aliases: %s" % ", ".join("`%s`" % a for a in aliases)
        lines.append(entry)
        if name in NOTES:
            lines.append("  - " + NOTES[name])
    lines.append("")

    lines += [
        "## Alias table",
        "",
        "%d aliases resolve to canonical parameters:" % len(PARAM_ALIASES),
        "",
        "| alias | canonical |",
        "|---|---|",
    ]
    for alias, canonical in sorted(PARAM_ALIASES.items()):
        lines.append("| `%s` | `%s` |" % (alias, canonical))
    lines.append("")
    return "\n".join(lines)


def main() -> int:
    text = render()
    if "--check" in sys.argv:
        on_disk = open(OUT).read() if os.path.exists(OUT) else ""
        if on_disk != text:
            sys.stderr.write(
                "docs/Parameters.md is stale — regenerate with "
                "`python helpers/gen_param_docs.py`\n"
            )
            return 1
        return 0
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as fh:
        fh.write(text)
    print("wrote %s (%d lines)" % (OUT, text.count("\n")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
