"""Persistent AOT compiled-executable store (the warm-start tier).

Wraps jax's ``serialize_executable`` pair behind a content-addressed
on-disk store so a replica spawn pays XLA compilation **once per
(checkpoint geometry, runtime)** instead of once per process.  Layout
under ``root/``::

    <key>.exe    pickle((payload_bytes, in_tree, out_tree))
    <key>.json   manifest: key fields echoed + golden scores + params
                 fingerprint at serialize time (see serving.warmkey)

Both are written write→fsync→atomic-rename, so a crashed writer leaves
either a complete entry or none.  Loading is paranoid by construction:

* key-field echo mismatch (foreign/corrupt manifest) → ``WarmstartMiss``
* unpickle / ``deserialize_and_load`` failure → ``WarmstartMiss``
* every deserialized executable is then gated by the engine's
  golden-batch canary before it serves (bit-exact against the manifest
  scores when the params fingerprint matches)

A miss is *never* an error — callers count it and fall back to a fresh
``lower().compile()``, then ``save`` re-serializes so the next spawn
hits.  The store itself keeps no metrics; serving and backfill each
count hits/misses/fallbacks in their own registries.
"""

from __future__ import annotations

import logging
import os
import pickle
from typing import Any, Dict, Sequence, Tuple

from . import warmkey

log = logging.getLogger(__name__)


class WarmstartMiss(Exception):
    """Entry absent/foreign/undeserializable — count it, compile fresh."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(f"{reason}: {detail}" if detail else reason)
        self.reason = reason


class ExecutableStore:
    """Content-addressed store of serialized XLA executables."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)

    # -- paths ---------------------------------------------------------
    def exe_path(self, key: str) -> str:
        return os.path.join(self.root, key + ".exe")

    def manifest_path(self, key: str) -> str:
        return os.path.join(self.root, key + ".json")

    def __contains__(self, key: str) -> bool:
        return (os.path.exists(self.exe_path(key))
                and os.path.exists(self.manifest_path(key)))

    # -- load ----------------------------------------------------------
    def load(self, fields: Dict[str, Any],
             execution_devices: Sequence[Any]
             ) -> Tuple[Any, Dict[str, Any]]:
        """Deserialize the executable for ``fields`` onto
        ``execution_devices`` — the devices it was compiled for (jax binds
        a loaded executable to EVERY local device when given none, which
        breaks a one-device program on a multi-device host).

        Returns ``(compiled, manifest)`` or raises :class:`WarmstartMiss`
        with a loud reason.  The caller MUST still run the golden-batch
        canary before letting the executable serve.
        """
        key = warmkey.store_key(fields)
        mpath, epath = self.manifest_path(key), self.exe_path(key)
        if not (os.path.exists(mpath) and os.path.exists(epath)):
            raise WarmstartMiss("absent", key[:12])
        try:
            manifest = warmkey.read_manifest(mpath)
        except (OSError, ValueError) as e:
            raise WarmstartMiss("manifest-unreadable", f"{key[:12]}: {e}")
        # Defense in depth against foreign files parked under our name:
        # the manifest must echo the exact key fields we derived the hash
        # from, else the blob was serialized for a different program.
        if manifest.get("fields") != fields:
            raise WarmstartMiss("key-mismatch", key[:12])
        try:
            with open(epath, "rb") as f:
                payload, in_tree, out_tree = pickle.loads(f.read())
            from jax.experimental import serialize_executable
            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=execution_devices)
        except Exception as e:  # corrupt pickle, version skew, XLA reject
            raise WarmstartMiss("deserialize-failed", f"{key[:12]}: {e}")
        return compiled, manifest

    # -- save ----------------------------------------------------------
    def save(self, fields: Dict[str, Any], compiled: Any, *,
             golden_scores: Any, params_fingerprint: str,
             execution_devices: Sequence[Any]) -> bool:
        """Serialize ``compiled`` under its content key.

        Best-effort: serialization failures (unsupported backend, full
        disk) are logged and swallowed — the executable still serves
        from memory, the next spawn just recompiles.
        """
        key = warmkey.store_key(fields)
        try:
            from jax.experimental import serialize_executable
            payload, in_tree, out_tree = serialize_executable.serialize(compiled)
            # Round-trip proof BEFORE anything hits disk: an executable
            # that was itself loaded from XLA's persistent compilation
            # cache (the --compile-cache-dir fallback tier) serializes
            # to a payload its own deserializer rejects ("Symbols not
            # found") — parking it would turn every future spawn into a
            # loud fallback, so refuse it here and let that spawn ride
            # the compile-cache tier instead.
            serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=execution_devices)
            blob = pickle.dumps((payload, in_tree, out_tree))
            warmkey.write_atomic(self.exe_path(key), blob)
            manifest = {
                "schema": warmkey.WARMSTART_SCHEMA,
                "fields": fields,
                "key": key,
                "params_fingerprint": str(params_fingerprint),
                "golden_scores": warmkey.encode_array(golden_scores),
                "payload_bytes": len(blob),
            }
            warmkey.write_manifest(self.manifest_path(key), manifest)
            return True
        except Exception as e:  # never let persistence break serving
            log.warning("warmstart: serialize of %s failed: %s", key[:12], e)
            return False

    def refresh_manifest(self, fields: Dict[str, Any], *, golden_scores: Any,
                         params_fingerprint: str) -> None:
        """Re-stamp an existing entry's manifest for the current checkpoint
        (after a fingerprint-skew load passed the canary) so the *next*
        same-checkpoint spawn gets the bit-exact gate back."""
        key = warmkey.store_key(fields)
        try:
            manifest = warmkey.read_manifest(self.manifest_path(key))
            manifest["params_fingerprint"] = str(params_fingerprint)
            manifest["golden_scores"] = warmkey.encode_array(golden_scores)
            warmkey.write_manifest(self.manifest_path(key), manifest)
        except (OSError, ValueError) as e:  # pragma: no cover - best effort
            log.warning("warmstart: manifest refresh of %s failed: %s",
                        key[:12], e)
