#!/usr/bin/env bash
# Docs honesty check (wired into scripts/tier1.sh):
#   1. every package/module directly under src/repro/ is mentioned in
#      README.md or DESIGN.md;
#   2. every relative markdown link in tracked *.md files resolves;
#   3. every path-looking token in README.md shell snippets names a
#      real file, and every `python -m pkg.mod` names a real module;
#   4. no stale references: every `repro.x.y` dotted module and every
#      `src/repro/...` path mentioned in the docs (all but the CHANGES.md
#      history) still exists;
#   5. no orphan packages: every directory package under src/repro (any
#      depth) is mentioned in README.md or DESIGN.md.
set -euo pipefail
cd "$(dirname "$0")/.."

python - <<'EOF'
import os
import re
import subprocess
import sys

fail = []

# --- 1. package coverage -------------------------------------------------
readme = open("README.md").read()
design = open("DESIGN.md").read()
docs = readme + design
for entry in sorted(os.listdir("src/repro")):
    if entry.startswith("__"):
        continue
    name = entry.removesuffix(".py")
    if not re.search(rf"\b{re.escape(name)}\b", docs):
        fail.append(f"package src/repro/{entry} is mentioned in neither "
                    f"README.md nor DESIGN.md")

# --- 2. relative markdown links ------------------------------------------
# PAPER/PAPERS/SNIPPETS are generated paper-extract dumps (figure links
# point into the original arxiv source) — not repo docs; skip them.
GENERATED = {"PAPER.md", "PAPERS.md", "SNIPPETS.md", "ISSUE.md"}
md_files = [
    f for f in subprocess.run(
        ["git", "ls-files", "*.md"], capture_output=True, text=True,
        check=True,
    ).stdout.split()
    if os.path.basename(f) not in GENERATED
]
link_re = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
for md in md_files:
    base = os.path.dirname(md)
    for target in link_re.findall(open(md).read()):
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        path = os.path.normpath(os.path.join(base, target.split("#")[0]))
        if not os.path.exists(path):
            fail.append(f"{md}: broken relative link -> {target}")

# --- 3. README shell snippets name real files/modules ---------------------
snippets = re.findall(r"```bash\n(.*?)```", readme, flags=re.S)
for block in snippets:
    for line in block.splitlines():
        line = line.split("#")[0]
        for mod in re.findall(r"-m\s+([\w.]+)", line):
            rel = mod.replace(".", "/")
            if not any(os.path.exists(p) for p in (
                    f"src/{rel}.py", f"src/{rel}/__init__.py",
                    f"{rel}.py", f"{rel}/__init__.py")):
                fail.append(f"README snippet names missing module: {mod}")
        for tok in re.findall(r"[\w./-]+\.(?:sh|py)\b", line):
            if "/" in tok and not os.path.exists(tok):
                fail.append(f"README snippet names missing file: {tok}")

# --- 4. stale module / path references ------------------------------------
# CHANGES.md is the per-PR history: its past entries name code that a
# later PR removed, and they stay as written.
doc_texts = {md: open(md).read() for md in md_files
             if os.path.basename(md) != "CHANGES.md"}
for md, text in doc_texts.items():
    for path in set(re.findall(r"src/repro[\w/.-]*", text)):
        path = path.rstrip(".")           # sentence-final period
        if not os.path.exists(path):
            fail.append(f"{md}: stale path reference -> {path}")
    for dotted in set(re.findall(r"\brepro\.[\w.]+\b", text)):
        # accept any prefix that is a real module — trailing components
        # may be attributes (repro.core.oracle.count_embeddings_oracle)
        parts = dotted.split(".")
        ok = False
        while len(parts) >= 2 and not ok:
            rel = "/".join(parts)
            ok = any(os.path.exists(p) for p in (f"src/{rel}.py",
                                                 f"src/{rel}"))
            parts = parts[:-1]
        if not ok:
            fail.append(f"{md}: stale module reference -> {dotted}")

# --- 5. orphan packages (any depth, not just top level) --------------------
for dirpath, dirnames, filenames in os.walk("src/repro"):
    dirnames[:] = [d for d in dirnames if not d.startswith("__")]
    for d in dirnames:
        if not any(f.endswith(".py")
                   for f in os.listdir(os.path.join(dirpath, d))):
            continue
        if not re.search(rf"\b{re.escape(d)}\b", docs):
            fail.append(f"orphan package {os.path.join(dirpath, d)}: "
                        f"mentioned in neither README.md nor DESIGN.md")

if fail:
    print("docs_check FAILED:", file=sys.stderr)
    for f in fail:
        print(f"  - {f}", file=sys.stderr)
    sys.exit(1)
print(f"docs_check OK ({len(md_files)} md files, "
      f"{len(snippets)} README snippets)")
EOF
