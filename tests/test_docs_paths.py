"""Every file a document names in back-quotes exists.

The rule: a back-quoted token that ends in ``.py``, ``.sh``, ``.json`` or
``.md`` and holds no space, ``*``, ``<`` or ``{`` is a path from the root,
or the tail of a path under ``deepspeed_tpu/``, ``benchmark/``, ``tests/``
or ``docs/`` (``kv_cache.py``, ``lib/traffic.py``).  A document that names a
deleted script or a record that never existed fails here, not in front of a
reader."""
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "PERF.md", "docs/serving.md",
             "docs/observability.md", "docs/stages.md", "docs/jaxlint.md",
             "docs/checkpointing.md", "docs/elastic.md"]
SEARCHED = ("deepspeed_tpu", "benchmark", "tests", "docs")
#: files a run writes or a user supplies: named in the documents, never
#: committed
WRITTEN_AT_RUN_TIME = {"trace.json", "meta.json", "ds_config.json",
                       "flightrec_supervisor.json"}


@pytest.fixture(scope="module")
def tails():
    """Every path under the searched directories, from its top and from
    each directory below it: ``a/b/c.py`` gives itself, ``b/c.py``,
    ``c.py``."""
    tails = set()
    for top in SEARCHED:
        for dirpath, dirs, names in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            rel = os.path.relpath(dirpath, ROOT).split(os.sep)
            for n in names:
                parts = rel + [n]
                tails.update("/".join(parts[i:]) for i in range(len(parts)))
    return tails


@pytest.mark.parametrize("document", DOCUMENTS)
def test_named_files_exist(document, tails):
    with open(os.path.join(ROOT, document), encoding="utf-8") as f:
        text = f.read()
    named = {t for t in re.findall(r"`([^`\n]+)`", text)
             if t.endswith((".py", ".sh", ".json", ".md"))
             and not any(c in t for c in " *<{")}
    assert named, f"{document} names no file: is the rule still reading it?"
    missing = sorted(t for t in named - WRITTEN_AT_RUN_TIME
                     if not os.path.exists(os.path.join(ROOT, t))
                     and t not in tails)
    assert not missing, f"{document} names files that do not exist: {missing}"
