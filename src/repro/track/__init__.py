from repro.track.trackers import (  # noqa: F401
    AGGREGATE, BLOCK, CLIENT_PASS, DISPATCH, DRAW_BATCH, ENCODE, GNORM_KEY,
    HOST_SPANS, PHASES, SERVER_UPDATE, CompositeTracker, CsvTracker,
    JsonlTracker, MemoryTracker, NullTracker, StdoutTracker, Tracker,
    TrackerSpec, composite, emitter, get_tracker, make_tracker,
    register_tracker, registered_trackers, resolve_opts, scope, span, tether,
    with_grad_stats,
)
