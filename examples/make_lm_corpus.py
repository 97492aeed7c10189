"""Materialize a real byte-level LM corpus from in-env text.

The environment is offline, so the LM train-to-accuracy proof
uses genuine text that ships with the image: the Python
standard library's source files plus installed-package documentation — real,
human-written prose and code, ~tens of MB. Deterministic: files are collected
in sorted order, so every run (and every host) builds the identical corpus.

Usage:  python examples/make_lm_corpus.py [out_path] [max_mb]
        (defaults: ./runs/lm_corpus.txt, 24 MB)
The output feeds ``LM_CORPUS=<out_path> MODEL=lm ./run.sh``.
"""

from __future__ import annotations

import os
import sys

def _roots() -> list[tuple[str, tuple[str, ...]]]:
    """Real text roots, preference order: stdlib source (prose-rich
    docstrings), then installed-package docs. Derived from the running
    interpreter (sysconfig / site), not hardcoded image paths — portable
    across hosts. Sorted traversal => deterministic corpus."""
    import site
    import sysconfig

    roots: list[tuple[str, tuple[str, ...]]] = []
    stdlib = sysconfig.get_paths().get("stdlib")
    if stdlib:
        roots.append((stdlib, (".py",)))
    site_dirs: list[str] = []
    try:
        site_dirs = site.getsitepackages()
    except AttributeError:  # some embedded interpreters
        pass
    for d in site_dirs:
        for pkg, exts in (("numpy", (".py", ".rst", ".txt")), ("jax", (".py",))):
            p = os.path.join(d, pkg)
            if os.path.isdir(p):
                roots.append((p, exts))
    return roots


def collect(max_bytes: int) -> bytes:
    chunks: list[bytes] = []
    total = 0
    for root, exts in _roots():
        if total >= max_bytes or not os.path.isdir(root):
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            # prune skipped subtrees in place so os.walk never descends
            dirnames[:] = sorted(
                d for d in dirnames if d != "__pycache__" and not d.startswith("test")
            )
            for name in sorted(filenames):
                if not name.endswith(tuple(exts)):
                    continue
                path = os.path.join(dirpath, name)
                try:
                    with open(path, "rb") as f:
                        data = f.read()
                except OSError:
                    continue
                # Text files only: skip anything that does not decode.
                try:
                    data.decode("utf-8")
                except UnicodeDecodeError:
                    continue
                chunks.append(data)
                chunks.append(b"\n\n")
                total += len(data) + 2
                if total >= max_bytes:
                    break
            if total >= max_bytes:
                break
    return b"".join(chunks)[:max_bytes]


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else "./runs/lm_corpus.txt"
    max_mb = float(sys.argv[2]) if len(sys.argv) > 2 else 24.0
    data = collect(int(max_mb * 1e6))
    # A near-empty corpus "succeeds" here but fails obscurely in train_lm
    # (0 windows) — fail loudly at the source instead.
    minimum = min(int(max_mb * 1e6) // 4, 1_000_000)
    if len(data) < minimum:
        raise SystemExit(
            f"collected only {len(data):,} bytes (< {minimum:,}) — no usable "
            "text roots found on this host (checked stdlib + site-packages)"
        )
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "wb") as f:
        f.write(data)
    print(f"wrote {len(data):,} bytes of real in-env text to {out}")


if __name__ == "__main__":
    main()
