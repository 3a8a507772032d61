"""Event store facades for engine components.

Mirrors data/.../store/{PEventStore,LEventStore,Common}.scala: components refer
to apps by *name*; the facade resolves name -> (appId, channelId) through the
metadata store and delegates to the DAOs.  ``PEventStore`` is the training-side
seam and returns columnar EventFrames (→ BiMap → device_put); ``LEventStore``
is the serving-side row access used inside predict() for business rules.
"""

from __future__ import annotations

from datetime import datetime
from typing import Iterator, Sequence

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.datamap import PropertyMap
from predictionio_tpu.data.storage.base import EventFilter, EventFrame
from predictionio_tpu.data.storage.config import StorageRuntime, get_storage


class AppNotFoundError(KeyError):
    pass


class ChannelNotFoundError(KeyError):
    pass


def resolve_app(
    app_name: str, channel_name: str | None = None, storage: StorageRuntime | None = None
) -> tuple[int, int | None]:
    """Resolve app/channel names to ids (store/Common.scala)."""
    storage = storage or get_storage()
    app = storage.apps().get_by_name(app_name)
    if app is None:
        raise AppNotFoundError(f"Invalid app name {app_name!r}")
    if channel_name is None:
        return app.id, None
    for ch in storage.channels().get_by_appid(app.id):
        if ch.name == channel_name:
            return app.id, ch.id
    raise ChannelNotFoundError(
        f"Invalid channel name {channel_name!r} for app {app_name!r}"
    )


class PEventStore:
    """Bulk columnar reads for DataSources (store/PEventStore.scala:40,75)."""

    def __init__(self, storage: StorageRuntime | None = None):
        self.storage = storage or get_storage()

    def find(
        self,
        app_name: str,
        channel_name: str | None = None,
        start_time: datetime | None = None,
        until_time: datetime | None = None,
        entity_type: str | None = None,
        entity_id: str | None = None,
        event_names: Sequence[str] | None = None,
        target_entity_type: str | None = None,
        target_entity_id: str | None = None,
        columns: Sequence[str] | None = None,
        ordered: bool = True,
    ) -> EventFrame:
        """``columns`` and ``ordered`` describe what the DataSource reads
        of the frame (``PEvents.find``): a backend may use them to read
        less; the defaults are the full frame in time order."""
        app_id, channel_id = resolve_app(app_name, channel_name, self.storage)
        return self.storage.p_events().find(
            app_id,
            channel_id,
            EventFilter(
                start_time=start_time,
                until_time=until_time,
                entity_type=entity_type,
                entity_id=entity_id,
                event_names=tuple(event_names) if event_names else None,
                target_entity_type=target_entity_type,
                target_entity_id=target_entity_id,
            ),
            columns=columns,
            ordered=ordered,
        )

    def aggregate_properties(
        self,
        app_name: str,
        entity_type: str,
        channel_name: str | None = None,
        start_time: datetime | None = None,
        until_time: datetime | None = None,
        required: Sequence[str] | None = None,
    ) -> dict[str, PropertyMap]:
        app_id, channel_id = resolve_app(app_name, channel_name, self.storage)
        return self.storage.p_events().aggregate_properties(
            app_id,
            entity_type,
            channel_id=channel_id,
            start_time=start_time,
            until_time=until_time,
            required=required,
        )


class LEventStore:
    """Row-level reads for serving-time business rules (store/LEventStore.scala:76)."""

    def __init__(self, storage: StorageRuntime | None = None):
        self.storage = storage or get_storage()

    def find_by_entity(
        self,
        app_name: str,
        entity_type: str,
        entity_id: str,
        channel_name: str | None = None,
        event_names: Sequence[str] | None = None,
        target_entity_type: str | None = None,
        target_entity_id: str | None = None,
        start_time: datetime | None = None,
        until_time: datetime | None = None,
        limit: int | None = None,
        latest: bool = True,
    ) -> Iterator[Event]:
        app_id, channel_id = resolve_app(app_name, channel_name, self.storage)
        # the DAO-level point read: parquet answers this via segment and
        # row-group skipping (docs/data_plane.md), fast enough to sit on
        # the serving path
        return self.storage.l_events().find_by_entity(
            app_id,
            entity_type,
            entity_id,
            channel_id=channel_id,
            event_names=event_names,
            target_entity_type=target_entity_type,
            target_entity_id=target_entity_id,
            start_time=start_time,
            until_time=until_time,
            limit=limit,
            reversed=latest,
        )

    def find(
        self,
        app_name: str,
        channel_name: str | None = None,
        **kwargs,
    ) -> Iterator[Event]:
        app_id, channel_id = resolve_app(app_name, channel_name, self.storage)
        names = kwargs.pop("event_names", None)
        return self.storage.l_events().find(
            app_id,
            channel_id,
            EventFilter(
                event_names=tuple(names) if names else None, **kwargs
            ),
        )
