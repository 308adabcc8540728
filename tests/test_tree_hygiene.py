"""What the tree says about itself holds: a kernel flag that is defined
is read, a flag that is read is defined, and the files README.md points
a reader to are there."""

import ast
import glob
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "paddle_tpu")


def _parse(path):
    with open(path) as f:
        return ast.parse(f.read(), path)


def test_flags_read_are_defined_and_kernel_flags_defined_are_read():
    defined = {
        node.args[0].value
        for node in ast.walk(_parse(os.path.join(PACKAGE, "flags.py")))
        if isinstance(node, ast.Call)
        and getattr(node.func, "id", "") == "define_flag"}
    assert len(defined) >= 16, "flags.py defines its flags another way"
    read = {}
    for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"),
                          recursive=True):
        if path == os.path.join(PACKAGE, "flags.py"):
            continue
        for node in ast.walk(_parse(path)):
            if isinstance(node, ast.Attribute) and \
                    node.attr.startswith("FLAGS_"):
                read.setdefault(node.attr, f"{path}:{node.lineno}")
    unknown = {name: at for name, at in read.items()
               if name not in defined}
    assert not unknown, f"read but never defined: {unknown}"
    # a kernel's flag with no reader is a fork nobody can take
    unread = {name for name in defined
              if name.startswith("FLAGS_pallas_")} - set(read)
    assert not unread, f"defined in flags.py, read nowhere: {unread}"


_PATH = re.compile(r"^[\w.\-/]+$")
_SUFFIXES = (".py", ".md", ".json", ".jsonl", ".sh", ".toml")


def _names_a_file(token: str) -> bool:
    return bool(_PATH.match(token)) and (
        token.endswith(_SUFFIXES) or token.endswith("/"))


def test_every_repo_path_readme_names_exists():
    with open(os.path.join(REPO, "README.md")) as f:
        text = f.read()
    tokens = set(re.findall(r"`([^`\n]+)`", text))
    # the layout block names its entries at line starts
    block = re.search(r"## Layout\n\n```\n(.*?)```", text, re.S).group(1)
    tokens |= set(re.findall(r"^(\S+)\s", block, re.M))
    # and paths from a top-level directory written bare in the prose
    tokens |= set(re.findall(
        r"\b((?:docs|tools|tests|benchmark|paddle_tpu)/[\w\-/]+\.\w+)",
        text))
    paths = sorted(t for t in tokens if _names_a_file(t))
    assert len(paths) >= 15, paths
    # a module may be named from the package's root, as the code does
    missing = [p for p in paths
               if not os.path.exists(os.path.join(REPO, p))
               and not os.path.exists(os.path.join(PACKAGE, p))]
    assert not missing, f"README.md names files that are not there: " \
                        f"{missing}"
