"""host-sync: device->host fetches only inside the executor module set.

One stray host fetch on a serving path costs a full device round-trip
and stalls every request queued behind it.  The single-writer executor
modules are the ONLY code
allowed to call the synchronizing primitives:

  jax.device_get(...)        explicit device->host copy
  <x>.block_until_ready()    dispatch barrier
  np.asarray(...)            implicit copy when handed a device array
  jnp.ndarray.item() / float(arr[i])-style scalar reads on subscripts

Everything else (net/, discovery/, daemon, the object-path service)
must hand work to the executor and consume its host-side results.
"""
from __future__ import annotations

import ast
from typing import Iterable, List

from tools.gubguard.core import Checker, Finding, ModuleInfo, dotted_name

# Modules that ARE the executor / host-transfer layer.  Matching is by
# posix-relpath suffix so the checker works from any scan root.
ALLOWED_SUFFIXES = (
    "runtime/backend.py",
    "runtime/fastpath.py",
    # The gubstat sampler fetches census leaves on the executor thread
    # (run_in_executor), and the tenant ledger only
    # regroups arrays the fast lane already fetched — its np.asarray
    # calls are host->host (docs/observability.md).
    "runtime/gubstat.py",
    "runtime/checkpoint.py",
    # The tier manager's fetches run on its own worker thread
    # (docs/tiering.md), and the cold store
    # itself is pure host numpy — its np.asarray calls are host->host;
    # the request-path touch (note_access) is a set probe, no device
    # arrays in reach.
    "runtime/coldtier.py",
    "runtime/sketch_backend.py",
    "runtime/store.py",
    "parallel/sharded.py",
    "parallel/global_sync.py",
    "parallel/mesh.py",
    # Device-layer kernels and their host packers.
    "ops/",
    # Tooling / harnesses, not serving paths.
    "testing/",
    "cli/",
)

_SYNC_CALLS = {"jax.device_get", "np.asarray", "numpy.asarray"}


def _allowed(relpath: str) -> bool:
    for suf in ALLOWED_SUFFIXES:
        if suf.endswith("/"):
            if ("/" + relpath).find("/" + suf) != -1 or relpath.startswith(
                suf
            ):
                return True
        elif relpath.endswith(suf):
            return True
    return False


class HostSyncChecker(Checker):
    name = "host-sync"

    def check_module(self, mod: ModuleInfo) -> Iterable[Finding]:
        if _allowed(mod.relpath):
            return ()
        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            msg = self._classify(node)
            if msg:
                out.append(Finding(
                    checker=self.name, path=mod.relpath,
                    line=node.lineno, message=msg,
                ))
        return out

    @staticmethod
    def _classify(call: ast.Call) -> str:
        fn = call.func
        dn = dotted_name(fn)
        if dn in _SYNC_CALLS:
            return (
                f"'{dn}' is a device->host fetch; only the executor "
                "module set may synchronize (one fetch costs a full "
                "device round-trip on a serving path)"
            )
        if isinstance(fn, ast.Attribute) and fn.attr == "block_until_ready":
            return (
                "'.block_until_ready()' is a dispatch barrier; only the "
                "executor module set may synchronize"
            )
        if (
            isinstance(fn, ast.Name)
            and fn.id in ("float", "int", "bool")
            and len(call.args) == 1
            and isinstance(call.args[0], ast.Subscript)
        ):
            sub = call.args[0]
            # Array-style indexing only: `x[i]` / `x[0]` on a simple
            # receiver.  String keys, slices, and call results are
            # dict/str/tuple access, not device-array element reads.
            idx = sub.slice
            arrayish = (
                isinstance(sub.value, (ast.Name, ast.Attribute))
                and (
                    isinstance(idx, ast.Name)
                    or (
                        isinstance(idx, ast.Constant)
                        and isinstance(idx.value, int)
                    )
                )
            )
            if arrayish:
                return (
                    f"'{fn.id}(x[i])' concretizes one element; if x is "
                    "a device array this is a per-element host fetch — "
                    "batch the read in an executor module instead"
                )
        return ""
