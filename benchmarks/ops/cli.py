"""Op kind ``cli``: one `kart` command run in this process through the entry
point a user calls (``kart_tpu.cli.cli``), as chip_smoke.py's ``Smoke.kart``
does. ``{repo}`` and ``{out}`` in the traffic's argv are filled in; where
the argv names ``{out}`` the command's output is that file, else stdout."""

import json
import os


class Op:
    #: the environment of a host-twin run: every device route closed
    HOST_TWIN_ENV = {
        "KART_DIFF_BACKEND": "host_native",
        "KART_DIFF_DEVICE": "0",
        "KART_DIFF_SHARDED": "0",
    }
    #: settings that would force a route: a run with any of them set cannot
    #: stand for auto routing (chip_smoke.ROUTING_OVERRIDES)
    ROUTING_OVERRIDES = (
        "KART_DIFF_BACKEND", "KART_DIFF_DEVICE", "KART_DIFF_SHARDED",
        "KART_DIFF_ENGINE", "KART_NO_JAX", "KART_DEVICE_MIN_ROWS",
        "KART_SHARDED_MIN_ROWS", "KART_DEVICE_MIN_ENVELOPES",
        "KART_RESIDENT_MIN_ENVELOPES", "KART_STREAM_MIN_ROWS",
        "KART_DEVICE_BATCH_ROWS",
    )

    def __init__(self, traffic, repo_path, work):
        self.out = os.path.join(work, "op-output")
        self.to_file = "{out}" in traffic["argv"]
        self.argv = [a.format(repo=repo_path, out=self.out) for a in traffic["argv"]]
        self.trace_path = os.path.join(work, "op-spans.json")
        self.fallback_counter = traffic["fallback_counter"]
        from kart_tpu import telemetry as tm

        tm.enable(metrics=True)  # counters are no-ops until enabled

    def run(self, env=None):
        """Run the command; -> its exit code and stdout bytes. This is the
        timed call: nothing else happens in it."""
        from click.testing import CliRunner

        from kart_tpu.cli import cli

        result = CliRunner().invoke(cli, self.argv, env=env, catch_exceptions=False)
        return result.exit_code, result.stdout_bytes

    def output(self, stdout):
        """The bytes the command answered with (read outside its timing)."""
        if not self.to_file:
            return stdout
        with open(self.out, "rb") as f:
            return f.read()

    def spans(self, on):
        """Switch the program's span recording; events of the commands run
        since are taken by :meth:`take_spans`."""
        from kart_tpu import telemetry as tm

        tm.drain_events()
        if on:
            tm.enable(trace=True, trace_path=self.trace_path)
        else:
            tm.enable(trace=False)

    def take_spans(self):
        """Span events of the last command (the CLI writes its trace when
        the command closes), as Chrome trace events."""
        if not os.path.exists(self.trace_path):
            return []
        with open(self.trace_path) as f:
            events = json.load(f)["traceEvents"]
        os.remove(self.trace_path)
        return events

    def fallbacks(self):
        from kart_tpu import telemetry as tm

        return sum(
            v for (name, _), v in tm.counters_snapshot().items()
            if name == self.fallback_counter
        )
