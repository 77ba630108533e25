"""Cross-component contract checker: the string-keyed edges nothing
else verifies.

PRs 4-6 grew the system into a genuinely distributed stack held
together by string contracts: HTTP routes a daemon serves vs paths its
clients dial, metric families registered in stats/metrics.py vs names
alert rules and docs query, internal hop headers stamped on one side
vs parsed on the other, `WEED_*` env vars read vs documented. Python
checks none of these — the filer UI shipped a `/metrics` link its own
router 404'd for a whole PR, and a renamed metric would silently turn
an alert rule into a constant-false no-op. This pass extracts every
side of each contract into a registry and reports one-sided edges:

  contract-route        a literal path dialed by an in-repo client
                        (op/http_call, urlopen, shell commands,
                        announce loop, UI href) that NO dispatch table
                        serves; relative UI links must be served by
                        the SAME module's handler (that is exactly the
                        drift the filer UI bug rode in on)
  contract-metric       a metric name queried (ring rate_sum/quantile/
                        increase_sum, alert wiring) or documented that
                        no Registry call registers
  contract-metric-orphan a registered family with no writer and no
                        reader anywhere — it renders constant-zero
                        rows that LOOK like instrumentation
  contract-header       an internal hop header (x-weed-*, x-shard-*)
                        stamped but never parsed, or parsed but never
                        stamped
  contract-status-reason a literal status code passed to fast_reply
                        (or the _json/_html/_reply wrappers) missing
                        from util/httpd._REASON — the reply line lies
                        `200 OK`-style ("404 OK") to the peer
  contract-env          a `WEED_*` env var read in code but absent
                        from docs/OPERATIONS (operators cannot know
                        it), or documented but read nowhere (doc rot)
  contract-flag         a `-flag` token documented in docs that no
                        add_argument defines (doc rot), or a defined
                        flag with no help= text (the CLI's only
                        self-documentation)
  no-deadline           a raw urlopen() on a data-plane module
                        (server/client/filer/ec/qos/scrub/s3api/
                        webdav): it can never inherit the request's
                        X-Weed-Deadline budget (docs/CHAOS.md) the way
                        op.http_call and the gRPC Stub do, so a
                        multi-hop request outlives its caller's intent
                        there — migrate to http_call or state why the
                        bounded one-hop timeout suffices

Suppression uses the standard `# weedlint: ignore[rule] — reason`
mechanism; findings anchored in markdown use the same comment inside
`<!-- ... -->`.

Like every weedlint pass: precision over recall. Dynamic paths
(`f"/{fid}"`), constructed env names, and prefix-routed gateways (S3
bucket routing, WebDAV) are skipped, not guessed at.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field

from seaweedfs_tpu.analysis import (
    Finding,
    REPO_ROOT,
    const_str as _const_str,
    dotted_name as _dotted,
)
from seaweedfs_tpu.analysis.lockorder import PackageIndex, build_index

# handler base classes — a class deriving (transitively) from one of
# these owns a dispatch table; its methods are where route comparisons
# live (mirrors hotloop's entry-point discovery)
_HANDLER_BASES = {
    "FastHandler",
    "FastRequestMixin",
    "BaseHTTPRequestHandler",
    "StreamRequestHandler",
}

# module (repo-relative path substring) -> daemon key. Relative UI
# links and host-hinted client calls are checked against the daemon's
# own route set plus the mini-loop funnel, not the whole-cluster union
# — a route another daemon serves must not mask this daemon's 404.
_DAEMON_MODULES = {
    os.path.join("server", "master_server.py"): "master",
    os.path.join("server", "volume_server.py"): "volume",
    os.path.join("server", "volume_workers.py"): "volume",
    os.path.join("server", "filer_server.py"): "filer",
    os.path.join("s3api", "s3api_server.py"): "s3",
    os.path.join("webdav", "webdav_server.py"): "webdav",
}

# The mini-loop funnel (util/httpd.serve_connection/_serve_debug)
# serves these on EVERY daemon, before per-server routing; extracted
# from util/httpd.py like any other dispatch, but kept as their own
# daemon key so per-daemon checks can union them in.
_FUNNEL_DAEMON = "_funnel"

# client-call sites whose URLs leave the cluster — their paths belong
# to an external service's contract, not ours. Reasons are mandatory,
# mirroring hotloop._EXEMPT_QUALS.
_EXTERNAL_CLIENT_MODULES: dict[str, str] = {
    os.path.join("seaweedfs_tpu", "util", "etcd.py"): (
        "etcd v2/v3 HTTP API paths are etcd's contract"
    ),
    os.path.join("seaweedfs_tpu", "notification", "cloud_queues.py"): (
        "SQS/PubSub-style endpoints are the cloud provider's contract"
    ),
    os.path.join("seaweedfs_tpu", "replication", "cloud_sinks.py"): (
        "object-store sink endpoints are the cloud provider's contract"
    ),
    os.path.join("seaweedfs_tpu", "stats", "metrics.py"): (
        "the push loop POSTs to an external pushgateway "
        "(/metrics/job/<job> is its API, not ours)"
    ),
    os.path.join("seaweedfs_tpu", "s3api", "client.py"): (
        "S3 SDK client: bucket/key routing is dynamic by design"
    ),
    os.path.join("seaweedfs_tpu", "filesys"): (
        "filer paths are user namespace entries, not routes"
    ),
}

# -flag tokens that appear in docs but belong to EXTERNAL tools (the
# compiler, Go's race detector, pytest) — documented deliberately,
# never defined by our argparse surface.
_EXTERNAL_DOC_FLAGS: dict[str, str] = {
    "race": "Go's -race detector, cited as prior art in ANALYSIS.md",
    "fsanitize": "compiler flag in sanitizer-build recipes",
    "print": "cc -print-file-name in the ASan preload recipe",
    "rdonly": "mount(8) option in operational recipes",
    "Wall": "compiler flag: the C tier's production command line",
    "Wextra": "compiler flag: the C tier's production command line",
    "Werror": "compiler flag: the C tier's production command line",
}

_METRIC_NAME_RE = re.compile(r"\b[a-z][a-z0-9_]*_(?:total|seconds|bytes)\b")
_WEED_METRIC_RE = re.compile(r"\bweed_[a-z0-9_]+\b")
_ENV_VAR_RE = re.compile(r"\bWEED_[A-Z0-9_]+\b")
# the lookbehind rejects `X`-style prose where the "opening" backtick
# is really the CLOSING backtick of a previous code span
_DOC_FLAG_RE = re.compile(
    r"(?<![\w`])`-([a-zA-Z][a-zA-Z0-9]{2,})(?:[ =][^`]*)?`"
)
_HREF_RE = re.compile(r"""(?:href|src|action)=["'](/[^"'?#\s]*)""")
_INTERNAL_HEADER_RE = re.compile(r"^(x-weed-|x-shard-)", re.IGNORECASE)


@dataclass
class Site:
    path: str  # repo-relative
    line: int


@dataclass
class ContractRegistry:
    """Every side of every extracted contract, for --json dumps, the
    docs, and the cross-checks below."""

    # daemon -> {route -> [sites]} ; "_funnel" = mini-loop-served
    served: dict[str, dict[str, list[Site]]] = field(default_factory=dict)
    served_prefixes: dict[str, dict[str, list[Site]]] = field(
        default_factory=dict
    )
    # (kind "exact"|"prefix", path, daemon_hint|None, site)
    client_routes: list[tuple[str, str, str | None, Site]] = field(
        default_factory=list
    )
    metric_registered: dict[str, Site] = field(default_factory=dict)
    metric_var_names: dict[str, str] = field(default_factory=dict)
    metric_queried: dict[str, list[Site]] = field(default_factory=dict)
    metric_doc_refs: dict[str, list[Site]] = field(default_factory=dict)
    header_stamped: dict[str, list[Site]] = field(default_factory=dict)
    header_parsed: dict[str, list[Site]] = field(default_factory=dict)
    status_known: set[int] = field(default_factory=set)
    status_used: dict[int, list[Site]] = field(default_factory=dict)
    env_read: dict[str, list[Site]] = field(default_factory=dict)
    env_documented: dict[str, list[Site]] = field(default_factory=dict)
    flag_defined: dict[str, list[Site]] = field(default_factory=dict)
    flag_no_help: list[tuple[str, Site]] = field(default_factory=list)
    flag_documented: dict[str, list[Site]] = field(default_factory=dict)
    # raw urlopen() call sites on data-plane modules (no-deadline rule)
    deadline_bypass: list[Site] = field(default_factory=list)

    def to_dict(self) -> dict:
        def sites(lst):
            return [f"{s.path}:{s.line}" for s in lst]

        return {
            "served_routes": {
                d: sorted(rs) for d, rs in sorted(self.served.items())
            },
            "served_prefixes": {
                d: sorted(rs)
                for d, rs in sorted(self.served_prefixes.items())
            },
            "client_routes": sorted(
                {p for _k, p, _hint, _s in self.client_routes}
            ),
            "metrics_registered": sorted(self.metric_registered),
            "metrics_queried": sorted(self.metric_queried),
            "headers_stamped": sorted(self.header_stamped),
            "headers_parsed": sorted(self.header_parsed),
            "status_codes_known": sorted(self.status_known),
            "status_codes_used": sorted(self.status_used),
            "env_read": sorted(self.env_read),
            "env_documented": sorted(self.env_documented),
            "flags_defined": sorted(self.flag_defined),
            "flags_documented": sorted(self.flag_documented),
            "deadline_bypass": sites(self.deadline_bypass),
        }


# ---------------------------------------------------------------------------
# shared AST helpers


def _handler_class_names(index: PackageIndex) -> set[str]:
    out: set[str] = set()
    changed = True
    while changed:
        changed = False
        for cls in index.classes.values():
            if cls.name in out:
                continue
            if any(b in _HANDLER_BASES or b in out for b in cls.bases):
                out.add(cls.name)
                changed = True
    return out


def _daemon_for_path(rel_path: str) -> str | None:
    for suffix, daemon in _DAEMON_MODULES.items():
        if rel_path.endswith(suffix):
            return daemon
    if rel_path.endswith(os.path.join("util", "httpd.py")):
        return _FUNNEL_DAEMON
    return None


# ---------------------------------------------------------------------------
# (a) routes: served side


def _extract_served(index: PackageIndex, reg: ContractRegistry) -> None:
    """Route literals from every dispatch table: `path == "/x"`,
    `path in ("/a", "/b")`, `path.startswith("/pfx")` inside handler
    classes (plus util/httpd's funnel functions)."""
    handler_names = _handler_class_names(index)
    funnel_path_suffix = os.path.join("util", "httpd.py")

    def in_scope(rec) -> str | None:
        daemon = _daemon_for_path(rec.path)
        if rec.cls is not None and rec.cls in handler_names:
            return daemon or "other"
        if rec.path.endswith(funnel_path_suffix):
            return _FUNNEL_DAEMON
        return None

    for qual, fn in index.fn_nodes.items():
        rec = index.funcs.get(qual)
        if rec is None:
            continue
        daemon = in_scope(rec)
        if daemon is None:
            continue
        exact = reg.served.setdefault(daemon, {})
        prefixes = reg.served_prefixes.setdefault(daemon, {})
        for node in ast.walk(fn):
            if isinstance(node, ast.Compare):
                consts: list[tuple[str, int]] = []
                for comp in node.comparators:
                    s = _const_str(comp)
                    if s is not None:
                        consts.append((s, node.lineno))
                    elif isinstance(comp, (ast.Tuple, ast.List, ast.Set)):
                        for el in comp.elts:
                            s = _const_str(el)
                            if s is not None:
                                consts.append((s, node.lineno))
                s = _const_str(node.left)
                if s is not None:
                    consts.append((s, node.lineno))
                for s, line in consts:
                    if s.startswith("/"):
                        exact.setdefault(s, []).append(Site(rec.path, line))
            elif (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "startswith"
                and node.args
            ):
                s = _const_str(node.args[0])
                if s is not None and s.startswith("/"):
                    prefixes.setdefault(s, []).append(
                        Site(rec.path, node.lineno)
                    )


# ---------------------------------------------------------------------------
# (a) routes: client side


def _joined_template(node: ast.JoinedStr) -> str:
    """Render an f-string with \\x00 placeholders for formatted values."""
    out: list[str] = []
    for part in node.values:
        if isinstance(part, ast.Constant):
            out.append(str(part.value))
        else:
            out.append("\x00")
    return "".join(out)


def _url_to_path(template: str) -> tuple[str, str] | None:
    """(kind, path) out of a URL template, or None when it has no
    usable literal path. A placeholder directly after a literal path
    (`f"/scrub/trigger{qs}"`) degrades to a prefix check; a
    placeholder mid-path (`f"/{fid}"`) disqualifies it — precision
    over recall."""
    rest = template
    if "://" in rest:
        rest = rest.partition("://")[2]
        slash = rest.find("/")
        if slash < 0:
            return None
        host = rest[:slash]
        if "\x00" not in host and not host.startswith(
            ("127.0.0.1", "localhost", "[::1]")
        ):
            return None  # literal external host: not our contract
        rest = rest[slash:]
    elif rest.startswith("\x00"):
        # f"{master}/dir/assign?{q}" — host placeholder first
        slash = rest.find("/")
        if slash < 0:
            return None
        rest = rest[slash:]
    if not rest.startswith("/"):
        return None
    path = rest.partition("?")[0].partition("#")[0]
    # URLs embedded in rendered HTML carry markup right after the path
    path = re.split(r"""["'<>\s]""", path, maxsplit=1)[0]
    if "\x00" in path:
        prefix = path.partition("\x00")[0]
        if len(prefix) < 2:
            return None  # fully dynamic (`/{fid}`)
        return ("prefix", prefix)
    return ("exact", path) if path else None


_CLIENT_CALL_TAILS = {"http_call", "urlopen", "Request", "_pooled_request"}

# deadline plane (docs/CHAOS.md): modules on these data-plane paths
# must make internal hops through deadline-inheriting transports
# (op.http_call, pb/rpc.Stub). A raw urlopen there is flagged
# `no-deadline` unless suppressed with a reason.
_DEADLINE_SCOPE = tuple(
    os.path.join("seaweedfs_tpu", d) + os.sep
    for d in (
        "server", "client", "filer", "ec", "qos", "scrub", "s3api",
        "webdav",
    )
)
# words in a host placeholder's expression that mark it as a NETWORK
# location (so `f"{master}/dir/assign"` counts but `f"{dirpath}/x.json"`
# never does)
_HOSTISH = ("master", "filer", "url", "addr", "host", "server",
            "netloc", "location", "target", "peer", "leader")


def _extract_client_routes(
    index: PackageIndex, trees: dict[str, ast.Module],
    reg: ContractRegistry
) -> None:
    for rel_path, tree in trees.items():
        source = index.sources[rel_path]
        if any(
            rel_path.startswith(pfx) or rel_path == pfx
            for pfx in _EXTERNAL_CLIENT_MODULES
        ):
            continue
        sites: list[tuple[str, str, str | None, int]] = []
        in_client_arg: set[int] = set()  # id()s of client-call args
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                tail = _dotted(node.func).rsplit(".", 1)[-1]
                args = list(node.args) + [
                    kw.value for kw in node.keywords if kw.arg == "url"
                ]
                if tail in _CLIENT_CALL_TAILS:
                    for arg in args:
                        in_client_arg.add(id(arg))
                        # bare literal path args (_pooled_request)
                        s = _const_str(arg)
                        if s and s.startswith("/"):
                            sites.append(
                                ("exact", s.partition("?")[0], None,
                                 node.lineno)
                            )
                elif tail == "status_page":
                    # nav-link route lists rendered into every UI page
                    daemon = _daemon_for_path(rel_path)
                    for arg in node.args:
                        if not isinstance(arg, (ast.List, ast.Tuple)):
                            continue
                        els = [_const_str(e) for e in arg.elts]
                        if els and all(
                            s is not None and s.startswith("/")
                            for s in els
                        ):
                            for s in els:
                                sites.append(
                                    ("exact", s, daemon or "relative",
                                     node.lineno)
                                )
            if isinstance(node, ast.JoinedStr):
                template = _joined_template(node)
                if "://" in template:
                    hit = _url_to_path(template)
                elif id(node) in in_client_arg and template.startswith(
                    "\x00"
                ):
                    # host-placeholder-first form, only inside a known
                    # client call and only with a host-shaped expr
                    hit = (
                        _url_to_path(template)
                        if _host_hint(node) is not None
                        or _looks_hosty(node)
                        else None
                    )
                else:
                    hit = None
                if hit is not None:
                    kind, path = hit
                    sites.append(
                        (kind, path, _host_hint(node), node.lineno)
                    )
            elif isinstance(node, ast.Constant) and isinstance(
                node.value, str
            ):
                s = node.value
                if (
                    s.startswith("http://")
                    and "\n" not in s
                    and " " not in s
                ):
                    hit = _url_to_path(s)
                    if hit is not None:
                        sites.append(
                            (hit[0], hit[1], None, node.lineno)
                        )
        for kind, path, hint, line in sites:
            reg.client_routes.append(
                (kind, path, hint, Site(rel_path, line))
            )
        # UI links: every href/src/action in rendered HTML templates is
        # a client-side route consumer — RELATIVE to the serving module
        daemon = _daemon_for_path(rel_path)
        for i, text in enumerate(source.splitlines(), start=1):
            for m in _HREF_RE.finditer(text):
                reg.client_routes.append(
                    ("exact", m.group(1), daemon or "relative",
                     Site(rel_path, i))
                )


def _looks_hosty(node: ast.JoinedStr) -> bool:
    for part in node.values:
        if isinstance(part, ast.FormattedValue):
            blob = ast.dump(part.value).lower()
            return any(w in blob for w in _HOSTISH)
        if isinstance(part, ast.Constant) and "/" in str(part.value):
            return False
    return False


def _host_hint(node: ast.JoinedStr) -> str | None:
    """Which daemon an f-string URL dials, inferred from the HOST
    placeholder's source expression (`f"http://{env.master}/..."` →
    master). Only the placeholder(s) before the first literal '/' are
    the host."""
    host_exprs: list[str] = []
    for part in node.values:
        if isinstance(part, ast.Constant):
            s = str(part.value)
            if "/" in s and not s.endswith("://") and s != "http://":
                break
        elif isinstance(part, ast.FormattedValue):
            host_exprs.append(ast.dump(part.value).lower())
    blob = " ".join(host_exprs)
    if "master" in blob:
        return "master"
    if "filer" in blob:
        return "filer"
    return None


# ---------------------------------------------------------------------------
# (b) metrics


_REGISTRY_FACTORY_TAILS = {"counter", "gauge", "histogram"}
_RING_QUERY_TAILS = {"rate_sum", "increase_sum", "quantile", "series"}
_METRIC_SUFFIX_STRIP = ("_bucket", "_sum", "_count")


def _base_metric(name: str) -> str:
    for sfx in _METRIC_SUFFIX_STRIP:
        if name.endswith(sfx):
            return name[: -len(sfx)]
    return name


def _extract_metrics(
    trees: dict[str, ast.Module], reg: ContractRegistry
) -> None:
    for rel_path, tree in trees.items():
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ):
                call = node.value
                tail = _dotted(call.func).rsplit(".", 1)[-1]
                if tail in _REGISTRY_FACTORY_TAILS and call.args:
                    name = _const_str(call.args[0])
                    if name and "_" in name:
                        reg.metric_registered[name] = Site(
                            rel_path, node.lineno
                        )
                        if len(node.targets) == 1 and isinstance(
                            node.targets[0], ast.Name
                        ):
                            reg.metric_var_names[name] = node.targets[0].id
            if isinstance(node, ast.Call):
                tail = _dotted(node.func).rsplit(".", 1)[-1]
                if tail in _RING_QUERY_TAILS and node.args:
                    name = _const_str(node.args[0])
                    if name:
                        reg.metric_queried.setdefault(
                            _base_metric(name), []
                        ).append(Site(rel_path, node.lineno))


def _extract_doc_metrics(
    docs: dict[str, str], reg: ContractRegistry
) -> None:
    for rel_path, text in docs.items():
        for i, line in enumerate(text.splitlines(), start=1):
            for m in _WEED_METRIC_RE.finditer(line):
                reg.metric_doc_refs.setdefault(
                    _base_metric(m.group(0)), []
                ).append(Site(rel_path, i))


# ---------------------------------------------------------------------------
# (c) headers + status codes


def _module_str_constants(tree: ast.Module) -> dict[str, str]:
    out: dict[str, str] = {}
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
        ):
            s = _const_str(node.value)
            if s is not None:
                out[node.targets[0].id] = s
    return out


def _global_attr_constants(trees: dict[str, ast.Module]) -> dict[str, str]:
    """UPPER_CASE module-level string constants by bare name across the
    whole package, kept only when every definition agrees — so
    `_trace.TRACE_HEADER` resolves from any module."""
    values: dict[str, set[str]] = {}
    for tree in trees.values():
        for name, s in _module_str_constants(tree).items():
            if name.isupper():
                values.setdefault(name, set()).add(s)
    return {n: next(iter(v)) for n, v in values.items() if len(v) == 1}


def _local_alias_constants(
    tree: ast.Module, global_attrs: dict[str, str]
) -> dict[str, str]:
    """Name → string for EVERY simple assignment in the file, any
    scope: `trace_hdr_key = _trace.TRACE_HEADER` makes the later
    `.get(trace_hdr_key)` resolvable."""
    out: dict[str, str] = {}
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
        ):
            continue
        s = _const_str(node.value)
        if s is None and isinstance(node.value, ast.Attribute):
            s = global_attrs.get(node.value.attr)
        if s is None and isinstance(node.value, ast.Name):
            s = global_attrs.get(node.value.id)
        if s is not None:
            out[node.targets[0].id] = s
    return out


_PARSE_TAILS = {"get", "pop", "getheader"}
_STAMP_TAILS = {"send_header", "add_header", "putheader", "setdefault"}
_REPLY_TAILS = {"fast_reply", "_reply", "_json", "_html", "_err"}


def _extract_headers_and_statuses(
    trees: dict[str, ast.Module], reg: ContractRegistry
) -> None:
    # resolve TRACE_HEADER-style constants — module-level, cross-module
    # attribute (`_trace.TRACE_HEADER`), and local aliases
    # (`trace_hdr_key = _trace.TRACE_HEADER`) — so `headers[HDR] = v`
    # and `.get(trace_hdr_key)` count as stamp/parse sites
    global_attrs = _global_attr_constants(trees)
    const_maps: dict[str, dict[str, str]] = {
        rel: _local_alias_constants(tree, global_attrs)
        for rel, tree in trees.items()
    }

    def header_name(node: ast.expr, rel_path: str) -> str | None:
        s = _const_str(node)
        if s is None and isinstance(node, ast.Name):
            s = const_maps.get(rel_path, {}).get(node.id) or global_attrs.get(
                node.id
            )
        if s is None and isinstance(node, ast.Attribute):
            s = global_attrs.get(node.attr)
        if s is not None and _INTERNAL_HEADER_RE.match(s):
            return s.lower()
        return None

    for rel_path, tree in trees.items():
        for node in ast.walk(tree):
            # headers.get("x-weed-trace") / headers.pop(...) / the
            # `"x-shard-hop" in headers` membership probe
            if isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute
            ):
                tail = node.func.attr
                if tail in _PARSE_TAILS and node.args:
                    h = header_name(node.args[0], rel_path)
                    if h:
                        reg.header_parsed.setdefault(h, []).append(
                            Site(rel_path, node.lineno)
                        )
                elif tail in _STAMP_TAILS and node.args:
                    h = header_name(node.args[0], rel_path)
                    if h:
                        reg.header_stamped.setdefault(h, []).append(
                            Site(rel_path, node.lineno)
                        )
                if tail in _REPLY_TAILS:
                    for arg in node.args:
                        if (
                            isinstance(arg, ast.Constant)
                            and isinstance(arg.value, int)
                            and not isinstance(arg.value, bool)
                            and 100 <= arg.value <= 599
                        ):
                            reg.status_used.setdefault(
                                arg.value, []
                            ).append(Site(rel_path, node.lineno))
            elif isinstance(node, ast.Compare):
                # `"x-shard-hop" in headers` and `k == TRACE_HEADER`
                # are both parse-side probes
                for side in [node.left] + list(node.comparators):
                    h = header_name(side, rel_path)
                    if h:
                        reg.header_parsed.setdefault(h, []).append(
                            Site(rel_path, node.lineno)
                        )
            elif (
                isinstance(node, ast.Tuple)
                and len(node.elts) == 2
                and not isinstance(node.ctx, ast.Store)
            ):
                # gRPC invocation metadata: ((TRACE_HEADER, v),)
                h = header_name(node.elts[0], rel_path)
                if h:
                    reg.header_stamped.setdefault(h, []).append(
                        Site(rel_path, node.lineno)
                    )
            elif isinstance(node, ast.Subscript):
                h = header_name(node.slice, rel_path)
                if h:
                    bucket = (
                        reg.header_stamped
                        if isinstance(node.ctx, ast.Store)
                        else reg.header_parsed
                    )
                    bucket.setdefault(h, []).append(
                        Site(rel_path, node.lineno)
                    )
            elif isinstance(node, ast.Dict):
                # outbound header dict literals: {"x-shard-hop": "1"}
                for key in node.keys:
                    if key is None:
                        continue
                    h = header_name(key, rel_path)
                    if h:
                        reg.header_stamped.setdefault(h, []).append(
                            Site(rel_path, node.lineno)
                        )
        # _REASON: the one status→reason table fast_reply renders from
        if rel_path.endswith(os.path.join("util", "httpd.py")):
            for node in ast.walk(tree):
                if (
                    isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == "_REASON"
                    and isinstance(node.value, ast.Dict)
                ):
                    for key in node.value.keys:
                        if isinstance(key, ast.Constant) and isinstance(
                            key.value, int
                        ):
                            reg.status_known.add(key.value)


# ---------------------------------------------------------------------------
# (d) env vars + CLI flags


def _extract_env_reads(
    trees: dict[str, ast.Module], reg: ContractRegistry
) -> None:
    for rel_path, tree in trees.items():
        for node in ast.walk(tree):
            name = None
            if isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                if (
                    dotted.endswith("environ.get")
                    or dotted.rsplit(".", 1)[-1] == "getenv"
                ) and node.args:
                    name = _const_str(node.args[0])
            elif isinstance(node, ast.Subscript) and _dotted(
                node.value
            ).endswith("environ"):
                name = _const_str(node.slice)
            if name and _ENV_VAR_RE.fullmatch(name):
                reg.env_read.setdefault(name, []).append(
                    Site(rel_path, node.lineno)
                )


def _extract_flags(
    trees: dict[str, ast.Module], reg: ContractRegistry
) -> None:
    for rel_path, tree in trees.items():
        for node in ast.walk(tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"
                and node.args
            ):
                continue
            flag = _const_str(node.args[0])
            if not flag or not flag.startswith("-"):
                continue
            name = flag.lstrip("-")
            site = Site(rel_path, node.lineno)
            reg.flag_defined.setdefault(name, []).append(site)
            has_help = any(
                kw.arg == "help"
                and not (
                    isinstance(kw.value, ast.Constant)
                    and not kw.value.value
                )
                for kw in node.keywords
            )
            if not has_help:
                reg.flag_no_help.append((name, site))


def _extract_docs(
    docs: dict[str, str], reg: ContractRegistry
) -> None:
    for rel_path, text in docs.items():
        for i, line in enumerate(text.splitlines(), start=1):
            for m in _ENV_VAR_RE.finditer(line):
                reg.env_documented.setdefault(m.group(0), []).append(
                    Site(rel_path, i)
                )
            for m in _DOC_FLAG_RE.finditer(line):
                reg.flag_documented.setdefault(m.group(1), []).append(
                    Site(rel_path, i)
                )


# ---------------------------------------------------------------------------
# registry assembly


_DOC_FILES = ("OPERATIONS.md", "README.md")


def _load_docs(repo_root: str) -> dict[str, str]:
    docs: dict[str, str] = {}
    candidates = [os.path.join(repo_root, n) for n in _DOC_FILES]
    docs_dir = os.path.join(repo_root, "docs")
    if os.path.isdir(docs_dir):
        candidates += [
            os.path.join(docs_dir, n)
            for n in sorted(os.listdir(docs_dir))
            if n.endswith(".md")
        ]
    for path in candidates:
        try:
            with open(path, "r", encoding="utf-8") as f:
                docs[os.path.relpath(path, repo_root)] = f.read()
        except OSError:
            continue
    return docs


def _load_extra_sources(repo_root: str) -> dict[str, str]:
    """tests/conftest.py reads WEED_* vars and references metric names;
    it is part of the operational contract surface."""
    rel = os.path.join("tests", "conftest.py")
    try:
        with open(os.path.join(repo_root, rel), "r", encoding="utf-8") as f:
            return {rel: f.read()}
    except OSError:
        return {}


def _parse_all(sources: dict[str, str]) -> dict[str, ast.Module]:
    trees: dict[str, ast.Module] = {}
    for rel_path, source in sources.items():
        try:
            trees[rel_path] = ast.parse(source)
        except SyntaxError:
            continue
    return trees


def _extract_deadline_bypass(
    trees: dict[str, ast.Module], reg: ContractRegistry
) -> None:
    """urlopen() calls on data-plane modules: the transports that
    inherit the ambient X-Weed-Deadline (op.http_call, rpc.Stub) do so
    by construction, so the only statically-detectable bypass is a raw
    urlopen — which has no deadline seam at all."""
    for rel_path, tree in trees.items():
        if not rel_path.startswith(_DEADLINE_SCOPE):
            continue
        if any(
            rel_path.startswith(pfx) or rel_path == pfx
            for pfx in _EXTERNAL_CLIENT_MODULES
        ):
            continue  # external-service clients: not our deadline plane
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Call)
                and _dotted(node.func).rsplit(".", 1)[-1] == "urlopen"
            ):
                reg.deadline_bypass.append(Site(rel_path, node.lineno))


def _check_deadline(reg: ContractRegistry) -> list[Finding]:
    return [
        Finding(
            "no-deadline",
            s.path,
            s.line,
            "raw urlopen() on a data-plane module cannot inherit the "
            "request's X-Weed-Deadline budget (docs/CHAOS.md) — a "
            "multi-hop request outlives its caller's intent here; use "
            "op.http_call / the gRPC Stub, or state why the bounded "
            "one-hop timeout suffices",
        )
        for s in reg.deadline_bypass
    ]


def build_registry(
    index: PackageIndex,
    docs: dict[str, str] | None = None,
    extra_sources: dict[str, str] | None = None,
) -> ContractRegistry:
    reg = ContractRegistry()
    # one parse per file, shared by every extractor (build_index's own
    # trees aren't kept, so this is the tier's single parse pass)
    trees = _parse_all(index.sources)
    extra_trees = _parse_all(extra_sources) if extra_sources else {}
    _extract_served(index, reg)
    _extract_client_routes(index, trees, reg)
    _extract_metrics(trees, reg)
    _extract_headers_and_statuses(trees, reg)
    _extract_env_reads(trees, reg)
    _extract_flags(trees, reg)
    _extract_deadline_bypass(trees, reg)
    if extra_trees:
        _extract_env_reads(extra_trees, reg)
        _extract_flags(extra_trees, reg)
    if docs:
        _extract_doc_metrics(docs, reg)
        _extract_docs(docs, reg)
    return reg


# ---------------------------------------------------------------------------
# cross-checks


def _route_served(
    reg: ContractRegistry, kind: str, path: str, daemon: str | None
) -> bool:
    def in_daemon(d: str) -> bool:
        routes = reg.served.get(d, {})
        if path in routes:
            return True
        if any(
            path.startswith(pfx) for pfx in reg.served_prefixes.get(d, {})
        ):
            return True
        if kind == "prefix":
            # `f"/scrub/trigger{qs}"`: the literal prefix names the
            # route; a served route equal to (or extending) it matches
            return any(r.startswith(path) for r in routes)
        return False

    if daemon in (None, "other", "relative"):
        return any(
            in_daemon(d)
            for d in set(reg.served) | set(reg.served_prefixes)
        )
    return in_daemon(daemon) or in_daemon(_FUNNEL_DAEMON)


def _check_routes(reg: ContractRegistry) -> list[Finding]:
    findings: list[Finding] = []
    seen: set[tuple[str, str, int]] = set()
    for kind, path, hint, site in reg.client_routes:
        key = (path, site.path, site.line)
        if key in seen:
            continue
        seen.add(key)
        if _route_served(reg, kind, path, hint):
            continue
        scope = (
            f"the {hint} dispatch table"
            if hint and hint not in ("other", "relative")
            else "any dispatch table"
        )
        findings.append(
            Finding(
                "contract-route",
                site.path,
                site.line,
                f"client dials {path!r} but {scope} never serves it "
                f"(the consuming side of this hop will 404)",
            )
        )
    return findings


def _sources_blob_without(
    sources: dict[str, str], skip_suffix: str
) -> str:
    return "\n".join(
        src
        for rel, src in sources.items()
        if not rel.endswith(skip_suffix)
    )


def _check_metrics(
    reg: ContractRegistry,
    index: PackageIndex,
    extra_sources: dict[str, str] | None,
) -> list[Finding]:
    findings: list[Finding] = []
    registered = set(reg.metric_registered)
    # (1) queried/documented but never registered
    for name, sites in sorted(reg.metric_queried.items()):
        if name not in registered:
            for s in sites:
                findings.append(
                    Finding(
                        "contract-metric",
                        s.path,
                        s.line,
                        f"queries metric {name!r} that no Registry "
                        f"registers — the query returns empty forever "
                        f"(a renamed family silently disables this "
                        f"rule)",
                    )
                )
    for name, sites in sorted(reg.metric_doc_refs.items()):
        if name not in registered and _METRIC_NAME_RE.fullmatch(name):
            for s in sites:
                findings.append(
                    Finding(
                        "contract-metric",
                        s.path,
                        s.line,
                        f"documents metric {name!r} that no Registry "
                        f"registers (doc rot: operators will query a "
                        f"name that never exists)",
                    )
                )
    # (2) registered but written/read nowhere: constant-zero exposition.
    # Judged only for the registration module itself (any metrics.py, so
    # fixture trees exercise the rule) — ad-hoc registries elsewhere are
    # their own consumers.
    metrics_py = "metrics.py"
    blob = _sources_blob_without(index.sources, metrics_py)
    if extra_sources:
        blob += "\n" + "\n".join(extra_sources.values())
    for name, site in sorted(reg.metric_registered.items()):
        if not site.path.endswith(metrics_py):
            continue  # fixture/other registries judge themselves
        var = reg.metric_var_names.get(name)
        referenced = (
            name in reg.metric_queried
            or name in reg.metric_doc_refs
            or name in blob
            or bool(var and re.search(rf"\b{re.escape(var)}\b", blob))
        )
        if not referenced:
            findings.append(
                Finding(
                    "contract-metric-orphan",
                    site.path,
                    site.line,
                    f"metric {name!r} is registered but no code writes "
                    f"or reads it and no doc mentions it — it renders "
                    f"constant-zero rows that look like real "
                    f"instrumentation",
                )
            )
    return findings


def _check_headers(reg: ContractRegistry) -> list[Finding]:
    findings: list[Finding] = []
    for h, sites in sorted(reg.header_stamped.items()):
        if h not in reg.header_parsed:
            s = sites[0]
            findings.append(
                Finding(
                    "contract-header",
                    s.path,
                    s.line,
                    f"internal header {h!r} is stamped here but no "
                    f"consuming side ever parses it (dead bytes on "
                    f"every hop, or the parser was renamed away)",
                )
            )
    for h, sites in sorted(reg.header_parsed.items()):
        if h not in reg.header_stamped:
            s = sites[0]
            findings.append(
                Finding(
                    "contract-header",
                    s.path,
                    s.line,
                    f"internal header {h!r} is parsed here but no "
                    f"in-repo side ever stamps it (the branch below "
                    f"is dead, or the stamping side drifted)",
                )
            )
    return findings


def _check_statuses(reg: ContractRegistry) -> list[Finding]:
    if not reg.status_known:
        return []  # fixture trees without util/httpd.py
    findings: list[Finding] = []
    for code, sites in sorted(reg.status_used.items()):
        if code in reg.status_known:
            continue
        for s in sites:
            findings.append(
                Finding(
                    "contract-status-reason",
                    s.path,
                    s.line,
                    f"status {code} has no entry in util/httpd._REASON "
                    f'— fast_reply will emit "{code} OK" to the peer',
                )
            )
    return findings


def _check_env(reg: ContractRegistry) -> list[Finding]:
    findings: list[Finding] = []
    for name, sites in sorted(reg.env_read.items()):
        if name not in reg.env_documented:
            s = sites[0]
            findings.append(
                Finding(
                    "contract-env",
                    s.path,
                    s.line,
                    f"env var {name} is read here but documented "
                    f"nowhere (docs/OPERATIONS/README) — operators "
                    f"cannot discover the knob",
                )
            )
    for name, sites in sorted(reg.env_documented.items()):
        if name not in reg.env_read:
            s = sites[0]
            findings.append(
                Finding(
                    "contract-env",
                    s.path,
                    s.line,
                    f"env var {name} is documented here but no code "
                    f"reads it (doc rot: the knob does nothing)",
                )
            )
    return findings


def _check_flags(reg: ContractRegistry) -> list[Finding]:
    findings: list[Finding] = []
    defined = set(reg.flag_defined)
    for name, sites in sorted(reg.flag_documented.items()):
        if name in defined or name in _EXTERNAL_DOC_FLAGS:
            continue
        # docs write `-traceSlowMs`; argparse may define `-traceSlowMs`
        # or `--trace-slow-ms` — try the dashed normalization too
        dashed = re.sub(r"(?<!^)([A-Z])", r"-\1", name).lower()
        if dashed in defined:
            continue
        for s in sites:
            findings.append(
                Finding(
                    "contract-flag",
                    s.path,
                    s.line,
                    f"flag -{name} is documented here but no "
                    f"add_argument defines it (doc rot: the flag "
                    f"errors out)",
                )
            )
    for name, site in reg.flag_no_help:
        findings.append(
            Finding(
                "contract-flag",
                site.path,
                site.line,
                f"flag -{name} has no help= text — argparse --help is "
                f"the CLI's only self-documentation",
            )
        )
    return findings


# ---------------------------------------------------------------------------
# entry point


def check(
    root: str | None = None,
    index: PackageIndex | None = None,
    docs: dict[str, str] | None = None,
) -> tuple[list[Finding], PackageIndex, ContractRegistry]:
    """Returns (findings, index, registry). `docs` overrides the repo
    doc set (fixture trees pass their own or none)."""
    index = index or build_index(root)
    if root is None:
        if docs is None:
            docs = _load_docs(REPO_ROOT)
        extra = _load_extra_sources(REPO_ROOT)
    else:
        docs = docs or {}
        extra = None
    reg = build_registry(index, docs=docs, extra_sources=extra)
    findings: list[Finding] = []
    findings += _check_routes(reg)
    findings += _check_metrics(reg, index, extra)
    findings += _check_headers(reg)
    findings += _check_statuses(reg)
    findings += _check_env(reg)
    findings += _check_flags(reg)
    findings += _check_deadline(reg)
    # findings anchored outside the package (docs, tests/conftest.py)
    # need those texts in the suppression scan, or the documented
    # `# weedlint: ignore[...]` escape hatch silently does nothing for
    # them
    for rel, text in {**(docs or {}), **(extra or {})}.items():
        index.sources.setdefault(rel, text)
    return findings, index, reg
