"""C-tier hardening checks: the compiler as the shims' lint pass.

The native shims (crc32c.c, gf256.c, needle.c, post.c behind the
needle_ext.c binding) are the one part of the tree no Python-level
tool can see into — and the part that parses adversarial multipart
bytes with the GIL released. Three checks:

  c-warnings     every shim must compile clean under
                 -Wall -Wextra -Werror with the system compiler
                 (the same flags _build.py now ships with, so a
                 warning can never reach production silently — it
                 fails the build into the pure-Python fallback);
                 with WEED_NATIVE_SAN set, the sanitizer variant of
                 the build is what gets exercised
  gil-release    the extension's hot entry points (encode's big-
                 payload branch, decode's big-payload CRC, the whole
                 post span) must wrap their C work in
                 Py_BEGIN/END_ALLOW_THREADS — losing one of those
                 re-serializes every handler thread behind memcpy+CRC
  no-compiler    reported as a note, never a failure: hosts without a
                 toolchain run the pure-Python fallbacks and have no C
                 attack surface to lint
"""

from __future__ import annotations

import os
import subprocess
import sysconfig
import tempfile

from seaweedfs_tpu.analysis import Finding
from seaweedfs_tpu.native import _build

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native"
)

# (source, needs_python_includes); needle.c and post.c compile as part
# of the needle_ext.c translation unit, exactly as production builds them
_UNITS = (
    ("crc32c.c", False),
    ("gf256.c", False),
    ("needle_ext.c", True),
    ("serve_ext.c", True),
    ("syscount.c", False),
)


def _compiler() -> str | None:
    for cc in _build._COMPILERS:
        try:
            proc = subprocess.run(
                [cc, "--version"], capture_output=True, timeout=10
            )
        except (OSError, subprocess.TimeoutExpired):
            continue
        if proc.returncode == 0:
            return cc
    return None


def _rel(name: str) -> str:
    return os.path.join("seaweedfs_tpu", "native", name)


def check_warnings() -> list[Finding]:
    cc = _compiler()
    if cc is None:
        return []  # no toolchain: pure-Python fallbacks serve, nothing to lint
    # the sanitizer mode labels the finding: compile_cmd builds the
    # same variant, so a rejection message must say WHICH build broke.
    # (this line also fixes a latent NameError: the f-string below read
    # `mode` that no path ever defined — reachable only on a failing
    # compile, which is exactly when the diagnostics matter most)
    mode = _build.san_mode()
    paths = sysconfig.get_paths()
    py_inc = tuple(
        dict.fromkeys((paths["include"], paths["platinclude"]))
    )
    findings: list[Finding] = []
    for src, needs_py in _UNITS:
        out = tempfile.NamedTemporaryFile(suffix=".so", delete=False)
        out.close()
        # the shared helper IS the production command line — the lint
        # tier compiles exactly what load_ext ships
        cmd = _build.compile_cmd(
            cc,
            os.path.join(_NATIVE_DIR, src),
            out.name,
            includes=py_inc if needs_py else (),
        )
        try:
            proc = subprocess.run(cmd, capture_output=True, timeout=120)
        except (OSError, subprocess.TimeoutExpired) as e:
            findings.append(
                Finding("c-warnings", _rel(src), 1, f"compile failed: {e}")
            )
            continue
        finally:
            try:
                os.unlink(out.name)
            except OSError:
                pass
        if proc.returncode != 0:
            # surface the first few diagnostic lines with their own
            # file:line so the finding is actionable
            diag = proc.stderr.decode("utf-8", "replace")
            lines = [
                ln
                for ln in diag.splitlines()
                if ": error:" in ln or ": warning:" in ln
            ][:8] or diag.splitlines()[:4]
            findings.append(
                Finding(
                    "c-warnings",
                    _rel(src),
                    1,
                    f"{cc} -Wall -Wextra -Werror"
                    + (f" [{mode}]" if mode else "")
                    + " rejected the unit: "
                    + " | ".join(ln.strip() for ln in lines),
                )
            )
    return findings


# entry point -> marker that must appear between its definition and the
# next top-level definition (structural, not a parse: the shims are
# plain C with one function per concern)
_GIL_SPANS = (
    ("py_encode", "needle_ext.c"),
    ("py_decode", "needle_ext.c"),
    ("py_post", "needle_ext.c"),
    # the serving loop parks in epoll_wait for whole idle windows —
    # holding the GIL there would freeze every handler thread in the
    # process for the duration
    ("py_loop", "serve_ext.c"),
)


def check_gil_release() -> list[Finding]:
    findings: list[Finding] = []
    sources: dict[str, str] = {}
    for _, src_name in _GIL_SPANS:
        if src_name in sources:
            continue
        try:
            with open(
                os.path.join(_NATIVE_DIR, src_name), "r", encoding="utf-8"
            ) as f:
                sources[src_name] = f.read()
        except OSError:
            sources[src_name] = ""
    for fn, src_name in _GIL_SPANS:
        source = sources[src_name]
        if not source:
            continue
        start = source.find(f"*{fn}(")
        if start < 0:
            findings.append(
                Finding(
                    "gil-release",
                    _rel(src_name),
                    1,
                    f"hot entry point {fn}() not found in {src_name}",
                )
            )
            continue
        # the function body runs to the next PyObject * definition
        end = source.find("static PyObject *", start + 1)
        body = source[start : end if end > 0 else len(source)]
        if "Py_BEGIN_ALLOW_THREADS" not in body:
            line = source[:start].count("\n") + 1
            findings.append(
                Finding(
                    "gil-release",
                    _rel(src_name),
                    line,
                    f"{fn}() never releases the GIL: its C span "
                    f"serializes every handler thread behind the "
                    f"memcpy/CRC work",
                )
            )
    return findings


# ---------------------------------------------------------------------------
# shm-atomics: the GCRA bucket's cross-process protocol (weedrace v4)

# expressions that READ or WRITE through the shared tat slot array;
# `&weed_shm.tat[` (address-of, the slot-pointer computation) and
# assignment to the `weed_shm.tat` pointer itself are not accesses
_SLOT_ACCESS = ("*slot", "slot[", "weed_shm.tat[")


def check_shm_atomics(
    source: str | None = None,
    rel_path: str = os.path.join("seaweedfs_tpu", "native", "serve.c"),
) -> list[Finding]:
    """Every access to the mmap'd GCRA slot array must be a C11/GCC
    atomic builtin with an EXPLICIT memory order. The bucket is the one
    piece of state shared across `-workers` sibling PROCESSES with no
    lock (that lock-freedom is its crash-safety story — a sibling
    SIGKILLed mid-admit holds nothing), so a single plain load or store
    is a data race the compiler may tear, cache, or reorder at will.
    Structural, statement-granular: a statement touching `*slot` /
    `slot[...]` / `weed_shm.tat[...]` must name `__atomic_*` and an
    `__ATOMIC_` order. `source` overrides the tree's serve.c so a
    planted-bug test can prove the rule fires on a plain-store mutant."""
    if source is None:
        try:
            with open(
                os.path.join(_NATIVE_DIR, "serve.c"), "r", encoding="utf-8"
            ) as f:
                source = f.read()
        except OSError:
            return []  # no serve.c shipped: nothing to check
    findings: list[Finding] = []
    # statement granularity: split on ';' but keep line accounting
    line = 1
    for stmt in source.split(";"):
        stmt_line = line
        line += stmt.count("\n")
        # exempt the address-of slot-pointer computation and the
        # declaration whose `*` is part of the type, not a deref
        probe = stmt.replace("&weed_shm.tat[", "").replace(
            "int64_t *slot", ""
        )
        if not any(p in probe for p in _SLOT_ACCESS):
            continue
        # find the line of the first access within the statement
        first = min(
            (probe.find(p) for p in _SLOT_ACCESS if p in probe),
        )
        at = stmt_line + probe[:first].count("\n")
        if "__atomic_" not in stmt or "__ATOMIC_" not in stmt:
            findings.append(
                Finding(
                    "shm-atomics",
                    rel_path,
                    at,
                    "GCRA shm slot accessed without a C11 atomic "
                    "builtin + explicit memory order: a plain "
                    "load/store on cross-process mmap state is a data "
                    "race the compiler may tear or reorder "
                    "(docs/ANALYSIS.md v4, shm-atomics)",
                )
            )
    return findings


def check() -> list[Finding]:
    return check_warnings() + check_gil_release() + check_shm_atomics()
