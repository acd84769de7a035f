"""Env-knob drift lint: code vs README.

Every ``RAY_TPU_*`` environment variable referenced by code must have a
row in a README knob table, and every documented knob must still exist
in code.  Rounds 5–7 each removed dead knobs *by hand* after finding
them documented-but-unread (``RAY_TPU_ATTN_EXP2``,
``RAY_TPU_CE_BF16_RESID``, ``RAY_TPU_FUSED_CE``); this test automates
the drift check in both directions.

Scope: string literals in ``ray_tpu/**/*.py`` (AST
scan, docstrings excluded — prose mentions of removed knobs are fine)
against ``README.md`` markdown table rows (``| `RAY_TPU_X` | ... |``;
the ``RAY_TPU_FOO_BQ/BK`` shorthand expands to both spellings).
"""

import ast
import pathlib
import re

REPO = pathlib.Path(__file__).resolve().parent.parent
KNOB = re.compile(r"RAY_TPU_[A-Z0-9_]+")


def code_knobs():
    found = {}
    for f in sorted((REPO / "ray_tpu").rglob("*.py")):
        try:
            tree = ast.parse(f.read_text())
        except SyntaxError:
            continue
        docstrings = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.Module, ast.ClassDef,
                                 ast.FunctionDef, ast.AsyncFunctionDef)):
                body = node.body
                if (body and isinstance(body[0], ast.Expr)
                        and isinstance(body[0].value, ast.Constant)):
                    docstrings.add(id(body[0].value))
        for node in ast.walk(tree):
            if (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and id(node) not in docstrings):
                for name in KNOB.findall(node.value):
                    found.setdefault(name, set()).add(
                        str(f.relative_to(REPO)))
    return found


def readme_knobs():
    found = set()
    for line in (REPO / "README.md").read_text().splitlines():
        if not line.lstrip().startswith("|"):
            continue
        for token in re.findall(r"RAY_TPU_[A-Z0-9_]+(?:/[A-Z0-9]+)*",
                                line):
            base, *alts = token.split("/")
            found.add(base)
            stem = base.rsplit("_", 1)[0]
            for alt in alts:
                found.add(f"{stem}_{alt}")
    return found


def test_every_code_knob_is_documented():
    code = code_knobs()
    documented = readme_knobs()
    missing = {k: sorted(v) for k, v in sorted(code.items())
               if k not in documented}
    assert not missing, (
        "env knobs referenced in code but missing from the README knob "
        f"tables (add a row or delete the knob): {missing}")


def test_every_documented_knob_exists_in_code():
    stale = sorted(readme_knobs() - set(code_knobs()))
    assert not stale, (
        "README documents env knobs no code reads (the r05-r07 dead-"
        f"knob pattern — remove the rows): {stale}")
