"""Op kind ``cli_merge``: op kind ``cli`` (one `kart` command run in this
process through the entry point a user calls) with a deadline on the first
command. A merge command that does not fit the window once cannot be
measured in it, so where the first command — less the seconds jax spent
compiling in it, heard through ``jax.monitoring`` as ``run.py``'s
``CompileLog`` hears them: a cold compile cache must not trip it — took
longer than the traffic file's ``max_command_s``, the run ends there with a
non-zero exit code and no result, instead of repeating such a command a
window long. The host twin's environment is op kind ``cli``'s."""

import importlib.util
import os
import sys
import time


def _sibling(name):
    """benchmarks/ops/<name>.py, loaded as run.py loads an op kind."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("bench_ops_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the exit code of a run whose first command missed the deadline
TOO_SLOW = 4


class Op(_sibling("cli").Op):
    def __init__(self, traffic, repo_path, work):
        super().__init__(traffic, repo_path, work)
        self.max_command_s = float(traffic["max_command_s"])
        self.first = True
        self.compile_s = 0.0
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **kwargs):
        if event == COMPILE_EVENT and self.first:
            self.compile_s += duration

    def run(self, env=None):
        if not self.first:
            return super().run(env)
        t = time.perf_counter()
        answer = super().run(env)
        wall = time.perf_counter() - t
        self.first = False
        if wall - self.compile_s > self.max_command_s:
            print(
                f"the first command took {wall:.1f} s ({self.compile_s:.1f} s of "
                f"it compiling), more than max_command_s = {self.max_command_s:g}: "
                "no result",
                file=sys.stderr, flush=True,
            )
            raise SystemExit(TOO_SLOW)
        return answer
