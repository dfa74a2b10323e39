"""Host milliseconds per dispatch spent staging its inputs
(``launch/serve._stage_chunk`` and the transfers it starts) while no serve
program ran on the device: the staging the serve step waits for. From the
benchmark's ``stage`` spans and the serve program's runs in the trace; the
part of a span hidden under the running program costs nothing end to end
and is not counted."""


def read(run):
    spans = run.trace["host_spans_exposed"]["stage"] if run.trace else []
    return 1e3 * sum(spans) / len(spans) if spans else None
