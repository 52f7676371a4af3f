"""Action FSM.

Every lifecycle operation is an Action sharing one protocol
(ref: HS/actions/Action.scala:34-108):

    run() = validate() -> begin()  [write transient-state entry at base_id+1]
            -> op()                [the actual work]
            -> end()               [write final-state entry at base_id+2,
                                    recreate latestStable]

with telemetry events at start/success/failure. Optimistic concurrency: the
transient-entry write fails if another writer took the id first
(ref: Action.scala:49-55; IndexLogManager.scala:178-194). A failure mid-op
abandons the transient state; CancelAction recovers to the last stable state
(ref: HS/actions/CancelAction.scala:35-67).
"""

from __future__ import annotations

import time
from typing import Optional

from hyperspace_tpu.models import states
from hyperspace_tpu.models.data_manager import IndexDataManager
from hyperspace_tpu.models.log_entry import IndexLogEntry
from hyperspace_tpu.models.log_manager import IndexLogManager
from hyperspace_tpu.obs import spans
from hyperspace_tpu.telemetry.events import ActionEvent, emit_event


class HyperspaceActionException(Exception):
    pass


class ConcurrentModificationException(HyperspaceActionException):
    pass


class NoChangesException(HyperspaceActionException):
    """Signals a no-op refresh/optimize (ref: HS/actions/NoChangesException.scala)."""


class Action:
    transient_state: str = ""
    final_state: str = ""
    event_class = ActionEvent

    def __init__(self, session, log_manager: IndexLogManager, data_manager: Optional[IndexDataManager] = None):
        self.session = session
        self.log_manager = log_manager
        self.data_manager = data_manager
        self.base_id: int = -1

    # --- to be provided by concrete actions --------------------------------
    @property
    def index_name(self) -> str:
        raise NotImplementedError

    def validate(self) -> None:
        pass

    def op(self) -> None:
        raise NotImplementedError

    def log_entry(self) -> IndexLogEntry:
        """The final-state entry to persist at base_id + 2."""
        raise NotImplementedError

    def transient_log_entry(self) -> IndexLogEntry:
        """The transient entry; default = latest entry with transient state
        (ref: Action.scala begin)."""
        latest = self.log_manager.get_latest_log()
        if latest is None:
            raise HyperspaceActionException(f"Index {self.index_name!r} has no log to transition")
        latest.state = self.transient_state
        return latest

    # Actions whose final entry snapshots a fresh view of the source (create,
    # full/incremental refresh) record a source-version -> log-id history
    # entry; every other action only carries the history forward — recording
    # there would map new log ids onto stale logged versions (e.g. quick
    # refresh copies an entry whose versionAsOf predates the data it covers
    # via hybrid scan).
    records_source_version: bool = False

    def _enrich_final(self, final: IndexLogEntry, final_id: int) -> None:
        """Source-provider property enrichment at commit time (ref:
        CreateActionBase enriched props + DeltaLakeRelationMetadata's
        deltaVersions history)."""
        source = getattr(final, "source", None)
        if source is None or source.relation is None:
            return
        from hyperspace_tpu.sources.manager import HyperspaceException

        try:
            meta = self.session.provider_manager.create_relation_metadata(source.relation)
        except HyperspaceException:
            # no provider answers for this logged relation (e.g. builders
            # reconfigured since the index was created) — nothing to enrich
            return
        if meta is None:
            return
        prev = self.log_manager.get_log(self.base_id) if self.base_id >= 0 else None
        final.properties = meta.enrich_index_properties(
            dict(final.properties),
            log_id=final_id if self.records_source_version else None,
            previous_properties=(prev.properties if prev is not None else None),
        )

    def _cleanup_allocated_version(self) -> None:
        """Best-effort removal of a data version dir claimed by a failed
        action — it was never referenced by a committed log entry, and
        leaving it would permanently bump the version sequence per failure."""
        v = getattr(self, "_allocated_version", None)
        if v is None or self.data_manager is None:
            return
        try:
            self.data_manager.delete_version(v)
        except OSError:
            pass

    # --- protocol ----------------------------------------------------------
    def _emit(self, state: str, message: str = "") -> None:
        emit_event(
            self.session,
            self.event_class(index_name=self.index_name, state=state, message=message),
        )

    def run(self) -> IndexLogEntry:
        self.validate()
        self._emit("Started")
        latest = self.log_manager.get_latest_id()
        self.base_id = latest if latest is not None else -1
        try:
            # stage log-commit: the action's own log work on both sides of
            # op() — entries built (the final one lists the new data files)
            # and written
            with spans.stage("log-commit", "build"):
                entry = self.transient_log_entry()
                entry.timestamp = int(time.time() * 1000)
                if not self.log_manager.write_log(self.base_id + 1, entry):
                    raise ConcurrentModificationException(
                        f"Another operation is in progress on index {self.index_name!r} "
                        f"(log id {self.base_id + 1} already exists)."
                    )
            self.op()
            with spans.stage("log-commit", "build"):
                final = self.log_entry()
                final.state = self.final_state
                final.timestamp = int(time.time() * 1000)
                self._enrich_final(final, self.base_id + 2)
                if not self.log_manager.write_log(self.base_id + 2, final):
                    raise ConcurrentModificationException(
                        f"Failed to commit final state for index {self.index_name!r}."
                    )
                # the final entry is committed: the allocated data version is
                # now referenced, so a failure past this point (e.g.
                # latestStable write) must NOT delete it — readers fall back
                # to scanning the log and would find the ACTIVE entry
                # pointing at deleted files
                self._allocated_version = None
                self.log_manager.create_latest_stable_log(self.base_id + 2)
        except NoChangesException:
            raise
        except Exception as e:
            self._cleanup_allocated_version()
            self._emit("Failure", str(e))
            raise
        self._emit("Success")
        return final
