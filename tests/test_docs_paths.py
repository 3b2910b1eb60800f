"""The documents name files that exist: every back-ticked path in
``README.md`` and ``docs/*.md`` is the path, or the tail of the path, of a
file or directory of this repository. A PR that moves or deletes a file
fails here until the documents follow."""

import fnmatch
import glob
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_ENDINGS = (".py", ".md", ".json", ".jsonl", ".sh", ".yaml", ".yml", ".cpp",
            "/")
# Not paths of this tree: URLs, routes and absolute paths, home and
# environment expansions, placeholders.
_ELSEWHERE = re.compile(r"://|^[/~.]|[{}<>$]")
# Cited on purpose though not of this tree: the reference repository's own
# paths (docs/MIGRATION.md, docs/tenancy.md) and the git-ignored directory
# the checkpoint factory writes.
_KNOWN_ABSENT = {"InfrastructureDeployment/", "Cleanup/",
                 "APIManagement/create_async_api_management_api.sh",
                 "checkpoints/"}


@pytest.fixture(scope="module")
def tree() -> list[str]:
    """Files git tracks, with every directory above them ('a/b/'); in a
    checkout without git, the files on disk outside dot-directories."""
    try:
        files = subprocess.run(
            ["git", "ls-files"], cwd=REPO, capture_output=True, text=True,
            check=True).stdout.splitlines()
    except (OSError, subprocess.CalledProcessError):
        files = []
        for where, dirs, names in os.walk(REPO):
            dirs[:] = [d for d in dirs if not d.startswith(".")]
            files += [os.path.relpath(os.path.join(where, n), REPO)
                      for n in names]
    # git still lists a file deleted from the working tree and not staged.
    files = [f for f in files if os.path.exists(os.path.join(REPO, f))]
    dirs = {f[:i + 1] for f in files for i, c in enumerate(f) if c == "/"}
    return files + sorted(dirs)


def _cited(text: str) -> set[str]:
    cited = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = re.sub(r":\d+([-–,]\d+)*$", "", word.strip("()[],;:'\""))
            if ("/" in word and word.endswith(_ENDINGS)
                    and not _ELSEWHERE.search(word)
                    and word not in _KNOWN_ABSENT):
                cited.add(word)
    return cited


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_cited_path_exists(document, tree):
    with open(os.path.join(REPO, document)) as f:
        cited = _cited(f.read())
    missing = sorted(
        word for word in cited
        if not any(fnmatch.fnmatchcase(path, word)
                   or fnmatch.fnmatchcase(path, "*/" + word)
                   for path in tree))
    assert not missing, f"{document} cites paths that do not exist: {missing}"
