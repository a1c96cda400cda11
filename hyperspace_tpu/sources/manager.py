"""Source provider manager: dispatches each API to exactly one provider.

Reference contract: sources/FileBasedSourceProviderManager.scala:38-183 —
providers come from conf (comma-separated names); each call must be answered
by exactly one provider (error on 0 or >1, :117-155).
"""

from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, TypeVar

from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.exceptions import HyperspaceError
from hyperspace_tpu.index.log_entry import Relation
from hyperspace_tpu.plan.nodes import LogicalPlan, Scan
from hyperspace_tpu.sources.interfaces import (
    LAKE_DATA_FORMATS,
    FileBasedRelation,
    FileBasedSourceProvider,
)

T = TypeVar("T")

# Name → factory registry; lake providers register themselves on import
# (the conf-class-name reflection of FileBasedSourceProviderManager.scala:166-182).
PROVIDER_REGISTRY: Dict[str, Callable[[HyperspaceConf], FileBasedSourceProvider]] = {}


def register_provider(name: str,
                      factory: Callable[[HyperspaceConf], FileBasedSourceProvider]) -> None:
    PROVIDER_REGISTRY[name] = factory


def _builtin_providers() -> None:
    if "default" not in PROVIDER_REGISTRY:
        from hyperspace_tpu.sources.default.provider import DefaultFileBasedSource

        register_provider("default", DefaultFileBasedSource)
    if "delta" not in PROVIDER_REGISTRY:
        from hyperspace_tpu.sources.delta.provider import DeltaLakeSource

        register_provider("delta", DeltaLakeSource)
    if "iceberg" not in PROVIDER_REGISTRY:
        from hyperspace_tpu.sources.iceberg.provider import IcebergSource

        register_provider("iceberg", IcebergSource)


class FileBasedSourceProviderManager:
    def __init__(self, conf: HyperspaceConf, session=None) -> None:
        _builtin_providers()
        self._conf = conf
        names = [n.strip() for n in conf.source_providers.split(",") if n.strip()]
        unknown = [n for n in names if n not in PROVIDER_REGISTRY]
        if unknown:
            raise HyperspaceError(f"Unknown source providers: {unknown}")
        self._providers: List[FileBasedSourceProvider] = [
            PROVIDER_REGISTRY[n](conf) for n in names]
        if session is not None:
            # Providers that need session context (index-manager lookups for
            # closest_index) opt in via bind_session.
            for p in self._providers:
                if hasattr(p, "bind_session"):
                    p.bind_session(session)

    def _run(self, api: str, fn: Callable[[FileBasedSourceProvider], Optional[T]]) -> T:
        """Exactly-one-provider dispatch
        (FileBasedSourceProviderManager.scala:117-155)."""
        answers = [(p, r) for p in self._providers if (r := fn(p)) is not None]
        if len(answers) == 0:
            raise HyperspaceError(f"No source provider answered {api}")
        if len(answers) > 1:
            names = [p.name for p, _ in answers]
            raise HyperspaceError(f"Multiple source providers answered {api}: {names}")
        return answers[0][1]

    def is_supported_relation(self, scan: Scan) -> bool:
        try:
            return self._run("is_supported_relation",
                             lambda p: p.is_supported_relation(scan) or None)
        except HyperspaceError:
            return False

    def get_relation(self, scan: Scan) -> FileBasedRelation:
        return self._run("get_relation", lambda p: p.get_relation(scan))

    def source_versions(self, plan: LogicalPlan) -> Dict[str, int]:
        """Table path -> version of each versioned table ``plan`` reads.
        Plain file scans are skipped without a provider call, so they
        cost no listing."""
        out: Dict[str, int] = {}
        for scan in plan.leaf_relations():
            rel = scan.relation
            if rel.file_format.lower() not in LAKE_DATA_FORMATS \
                    or rel.index_scan_of is not None:
                continue
            relation = self.get_relation(scan)
            version = relation.source_version()
            if version is not None:
                out[os.path.abspath(relation.root_paths[0])] = version
        return out

    def internal_file_format_name(self, relation: Relation) -> str:
        return self._run("internal_file_format_name",
                         lambda p: p.internal_file_format_name(relation))

    def refresh_relation_metadata(self, relation: Relation) -> Relation:
        return self._run("refresh_relation_metadata",
                         lambda p: p.refresh_relation_metadata(relation))

    def enrich_index_properties(self, relation: Relation,
                                properties: Dict[str, str]) -> Dict[str, str]:
        return self._run("enrich_index_properties",
                         lambda p: p.enrich_index_properties(relation, properties))
