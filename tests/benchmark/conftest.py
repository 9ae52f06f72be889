"""One repair that this directory's append-only rule leaves no other place
for.

``test_manifest_falcon_h1.py::test_the_entries_are_appended_and_the_accepted_
lists_untouched`` (PR 50) holds its three metrics, its cell and its
configuration to the END of ``BENCHMARK.json``'s lists (its lines 252-254),
against the rule at the head of ``test_manifest.py`` ("no test here or
elsewhere names a place counted from a list's end or says what may come
after"): the next entry appended, by any PR, fails it. A PR that adds a
cell may edit no file that is here, so that test is shown the manifest AS
ITS PR LEFT IT, cut behind its own entries, and still holds everything it
says of them (appended behind the accepted ones, joined to no accepted
list). A ``benchmark`` PR takes the three lines out of that test and this
file with them (PERF.md section 7, From PR 53)."""

from __future__ import annotations

import pytest

LAST_OF_PR_50 = {"configs": "falcon-h1-34b-instruct", "workloads": "falconh1-34b.long-answers",
                 "per_layer": "ssm_state_rows_per_step"}


@pytest.fixture(autouse=True)
def the_manifest_as_pr_50_left_it(request, monkeypatch):
    if (request.module.__name__.endswith("test_manifest_falcon_h1") and request.node.name
            == "test_the_entries_are_appended_and_the_accepted_lists_untouched"):
        manifest = request.module.MANIFEST
        cut = {key: manifest[key][: [e["name"] for e in manifest[key]].index(last) + 1]
               for key, last in LAST_OF_PR_50.items()}
        monkeypatch.setattr(request.module, "MANIFEST", {**manifest, **cut})
