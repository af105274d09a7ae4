"""graft-trace: zero-dependency structured telemetry for the drive loop.

Import chain stays stdlib-only at package import (the `records` module
touches jax and is imported lazily by its users) so `fedml_tpu.telemetry`
is safe from any layer, including utils/ modules that load before jax is
configured.
"""

from fedml_tpu.telemetry.tracer import (  # noqa: F401
    EVENT_SCHEMAS,
    NULL_TRACER,
    NullTracer,
    Tracer,
    adopt,
    current_job,
    emit,
    gauge,
    get_tracer,
    install,
    job_scope,
    open_span_id,
    parse_profile_rounds,
    uninstall,
)
