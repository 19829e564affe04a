"""env-parity: the GUBER_*/GUBTRACE_* env surface must match docs + the
reference.

Three-way diff between

  parsed     -- GUBER_*/GUBTRACE_* string literals in the scanned
                python modules (core/config.py is the canonical parse
                site — gubtrace's knobs route through it too);
  referenced -- env tokens in README.md, docs/ and deploy/ (what we
                promise operators);
  reference  -- the Go reference daemon's env surface (config.go), the
                compatibility target (GUBER_* only; GUBTRACE_* is this
                build's tooling surface).

Rules:
  * referenced-but-not-parsed is an ERROR: a manifest or doc promises a
    knob the daemon silently ignores (the worst failure mode for a rate
    limiter — an operator "sets" a limit control and nothing happens);
  * reference-vars-not-parsed is a WARNING listing the untranslated
    set (the VERDICT parity gap), minus the vars that are structurally
    inapplicable to the TPU rebuild;
  * parsed-but-undocumented (absent from deploy/example.conf) is a
    WARNING: every supported knob must be discoverable.

The names in core/config.py's RETIRED_ENV (settings a daemon refuses
to start with) are not parsed: a doc that still promises one is the
same ERROR, and example.conf need not list them.

OTEL_* is an ACKNOWLEDGED external namespace, not drift: it is the
OpenTelemetry SDK's own env spec (runtime/tracing.py reads the subset
it implements; an attached OTel SDK reads more).  Docs may therefore
reference OTEL_ vars this repo never parses — only the
parsed-but-undocumented warning applies to them (an OTEL_ var our code
DOES read must still appear in deploy/example.conf).  The GUBER_*/
GUBTRACE_*/GUBPROOF_*/GUBRANGE_* rules stay strict and unchanged.
"""
from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, Iterable, List, Set

from tools.gubguard.core import Checker, Finding, ModuleInfo

_VAR_RE = re.compile(
    r"\b(?:GUBER|GUBTRACE|GUBPROOF|GUBRANGE)_[A-Z0-9_]+\b"
)
# The acknowledged external namespace: standard OpenTelemetry env vars
# (see module docstring).  Tracked separately so example.conf coverage
# of the vars we parse is still checked, but a documented-only OTEL_
# var is never flagged as a silent no-op.
_OTEL_RE = re.compile(r"\bOTEL_[A-Z0-9_]+\b")

# The Go reference daemon's env surface (config.go:253-504).  Vars the
# rebuild already parses are checked dynamically; this list exists so
# NEW reference vars that appear in neither code nor docs still get
# reported instead of silently drifting.
REFERENCE_VARS: Set[str] = {
    "GUBER_DEBUG", "GUBER_GRPC_ADDRESS", "GUBER_HTTP_ADDRESS",
    "GUBER_STATUS_HTTP_ADDRESS", "GUBER_ADVERTISE_ADDRESS",
    "GUBER_CACHE_SIZE", "GUBER_DATA_CENTER", "GUBER_METRIC_FLAGS",
    "GUBER_BATCH_TIMEOUT", "GUBER_BATCH_WAIT", "GUBER_BATCH_LIMIT",
    "GUBER_GLOBAL_TIMEOUT", "GUBER_GLOBAL_SYNC_WAIT",
    "GUBER_GLOBAL_BATCH_LIMIT",
    "GUBER_MULTI_REGION_TIMEOUT", "GUBER_MULTI_REGION_SYNC_WAIT",
    "GUBER_MULTI_REGION_BATCH_LIMIT",
    "GUBER_PEER_DISCOVERY_TYPE", "GUBER_PEERS", "GUBER_PEER_PICKER",
    "GUBER_PEER_PICKER_HASH", "GUBER_REPLICATED_HASH_REPLICAS",
    "GUBER_DNS_FQDN", "GUBER_DNS_POLL_INTERVAL", "GUBER_RESOLV_CONF",
    "GUBER_ETCD_KEY_PREFIX", "GUBER_ETCD_ENDPOINTS",
    "GUBER_ETCD_DIAL_TIMEOUT", "GUBER_ETCD_USER", "GUBER_ETCD_PASSWORD",
    "GUBER_ETCD_ADVERTISE_ADDRESS", "GUBER_ETCD_TLS_CA",
    "GUBER_ETCD_TLS_CERT", "GUBER_ETCD_TLS_KEY",
    "GUBER_ETCD_TLS_SKIP_VERIFY",
    "GUBER_K8S_NAMESPACE", "GUBER_K8S_ENDPOINTS_SELECTOR",
    "GUBER_K8S_POD_IP", "GUBER_K8S_POD_PORT",
    "GUBER_K8S_WATCH_MECHANISM",
    "GUBER_TLS_CA", "GUBER_TLS_CA_KEY", "GUBER_TLS_CERT",
    "GUBER_TLS_KEY", "GUBER_TLS_CLIENT_AUTH",
    "GUBER_TLS_CLIENT_AUTH_CA_CERT", "GUBER_TLS_CLIENT_AUTH_CERT_FILE",
    "GUBER_TLS_CLIENT_AUTH_KEY_FILE", "GUBER_TLS_INSECURE_SKIP_VERIFY",
    "GUBER_TLS_MIN_VERSION",
    "GUBER_GRPC_MAX_CONN_AGE_SEC", "GUBER_LOG_LEVEL",
    "GUBER_WORKER_COUNT", "GUBER_INSTANCE_ID",
    "GUBER_MEMBERLIST_ADDRESS", "GUBER_MEMBERLIST_ADVERTISE_ADDRESS",
}

# Reference vars with no analog in this architecture (documented in
# docs/invariants.md): the Go worker-pool and memberlist knobs.
INAPPLICABLE: Set[str] = {
    "GUBER_WORKER_COUNT",            # no Go worker pool; the device IS it
    "GUBER_MEMBERLIST_ADDRESS",      # memberlist -> gossip (GUBER_GOSSIP_*)
    "GUBER_MEMBERLIST_ADVERTISE_ADDRESS",
    "GUBER_INSTANCE_ID",
}

_DOC_GLOBS = ("README.md", "docs/**/*.md", "deploy/**/*")
_EXAMPLE_CONF = "deploy/example.conf"


class EnvParityChecker(Checker):
    name = "env-parity"

    def __init__(self) -> None:
        self.parsed: Set[str] = set()
        self.parsed_otel: Set[str] = set()
        self.retired: Set[str] = set()
        self.saw_config = False

    def check_module(self, mod: ModuleInfo) -> Iterable[Finding]:
        if mod.relpath.endswith("core/config.py"):
            self.saw_config = True
            for node in mod.tree.body:
                if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "RETIRED_ENV"
                    for t in node.targets
                ):
                    self.retired.update(
                        _VAR_RE.findall(ast.unparse(node.value))
                    )
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Constant) and isinstance(
                node.value, str
            ):
                self.parsed.update(_VAR_RE.findall(node.value))
                self.parsed_otel.update(_OTEL_RE.findall(node.value))
        return ()

    def finalize(self, root: Path) -> Iterable[Finding]:
        if not self.saw_config:
            # Partial scan (single file / subpackage): the parsed set is
            # incomplete, so a doc diff would be all false positives.
            return ()
        self.parsed -= self.retired
        referenced: Dict[str, List[str]] = {}
        for pattern in _DOC_GLOBS:
            for p in sorted(root.glob(pattern)):
                if not p.is_file():
                    continue
                try:
                    text = p.read_text(encoding="utf-8", errors="replace")
                except OSError:
                    continue
                rel = p.relative_to(root).as_posix()
                for var in set(_VAR_RE.findall(text)):
                    referenced.setdefault(var, []).append(rel)

        out: List[Finding] = []
        for var in sorted(referenced):
            # `GUBER_GOSSIP_*`-style wildcard prefixes and the bare
            # prefix aren't var names; INAPPLICABLE vars may appear in
            # docs as documented exemptions.
            if var.endswith("_") or var in INAPPLICABLE:
                continue
            if var not in self.parsed:
                where = ", ".join(referenced[var][:3])
                out.append(Finding(
                    checker=self.name, path=where.split(",")[0], line=1,
                    message=(
                        f"'{var}' is documented ({where}) but never "
                        "parsed — an operator setting it gets a silent "
                        "no-op"
                    ),
                ))

        untranslated = sorted(
            REFERENCE_VARS - self.parsed - INAPPLICABLE
        )
        if untranslated:
            out.append(Finding(
                checker=self.name, path="gubernator_tpu/core/config.py",
                line=1, severity="warning",
                message=(
                    f"{len(untranslated)} reference env vars not yet "
                    "translated: " + ", ".join(untranslated)
                ),
            ))

        conf = root / _EXAMPLE_CONF
        if conf.is_file():
            try:
                conf_text = conf.read_text(encoding="utf-8")
            except OSError:
                conf_text = ""
            doc_vars = set(_VAR_RE.findall(conf_text))
            undocumented = sorted(
                v for v in self.parsed - doc_vars if v != "GUBER_"
            )
            if undocumented:
                out.append(Finding(
                    checker=self.name, path=_EXAMPLE_CONF, line=1,
                    severity="warning",
                    message=(
                        "parsed but absent from example.conf: "
                        + ", ".join(undocumented)
                    ),
                ))
            # OTEL_* (acknowledged external namespace): only the vars
            # runtime/tracing.py actually READS must be discoverable in
            # example.conf — documented-only OTEL_ vars belong to the
            # OTel SDK's spec and are never drift.
            otel_doc = set(_OTEL_RE.findall(conf_text))
            otel_missing = sorted(self.parsed_otel - otel_doc)
            if otel_missing:
                out.append(Finding(
                    checker=self.name, path=_EXAMPLE_CONF, line=1,
                    severity="warning",
                    message=(
                        "OTEL_ vars read by the runtime but absent "
                        "from example.conf: " + ", ".join(otel_missing)
                    ),
                ))
        return out
