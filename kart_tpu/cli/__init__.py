"""The ``kart`` command surface (reference: kart/cli.py + per-command modules).

Run as ``python -m kart_tpu.cli`` (or ``python -m kart_tpu``). Commands are
grouped in modules and registered lazily so startup stays fast.
"""

import importlib
import os
import sys

import click

import kart_tpu

# command name -> module (lazy loading, reference: cli.py:21-43)
_COMMANDS = {
    "init": "kart_tpu.cli.repo_cmds",
    "import": "kart_tpu.cli.repo_cmds",
    "commit": "kart_tpu.cli.repo_cmds",
    "status": "kart_tpu.cli.repo_cmds",
    "checkout": "kart_tpu.cli.repo_cmds",
    "switch": "kart_tpu.cli.repo_cmds",
    "restore": "kart_tpu.cli.repo_cmds",
    "reset": "kart_tpu.cli.repo_cmds",
    "create-workingcopy": "kart_tpu.cli.repo_cmds",
    "diff": "kart_tpu.cli.diff_cmds",
    "log": "kart_tpu.cli.diff_cmds",
    "show": "kart_tpu.cli.diff_cmds",
    "create-patch": "kart_tpu.cli.diff_cmds",
    "apply": "kart_tpu.cli.diff_cmds",
    "branch": "kart_tpu.cli.ref_cmds",
    "tag": "kart_tpu.cli.ref_cmds",
    "config": "kart_tpu.cli.ref_cmds",
    "gc": "kart_tpu.cli.ref_cmds",
    "fsck": "kart_tpu.cli.ref_cmds",
    "reflog": "kart_tpu.cli.ref_cmds",
    "git": "kart_tpu.cli.ref_cmds",
    "data": "kart_tpu.cli.data_cmds",
    "query": "kart_tpu.cli.query_cmds",
    "meta": "kart_tpu.cli.data_cmds",
    "merge": "kart_tpu.cli.merge_cmds",
    "conflicts": "kart_tpu.cli.merge_cmds",
    "resolve": "kart_tpu.cli.merge_cmds",
    "clone": "kart_tpu.cli.remote_cmds",
    "push": "kart_tpu.cli.remote_cmds",
    "pull": "kart_tpu.cli.remote_cmds",
    "fetch": "kart_tpu.cli.remote_cmds",
    "remote": "kart_tpu.cli.remote_cmds",
    "serve": "kart_tpu.cli.remote_cmds",
    "serve-stdio": "kart_tpu.cli.remote_cmds",
    "spatial-filter": "kart_tpu.cli.spatial_cmds",
    "upgrade": "kart_tpu.cli.upgrade_cmds",
    "upgrade-to-kart": "kart_tpu.cli.upgrade_cmds",
    "upgrade-to-tidy": "kart_tpu.cli.upgrade_cmds",
    "commit-files": "kart_tpu.cli.data_cmds",
    "build-annotations": "kart_tpu.cli.data_cmds",
    "stats": "kart_tpu.cli.stats_cmds",
    "top": "kart_tpu.cli.top_cmds",
    "watch": "kart_tpu.cli.watch_cmds",
    "fleet": "kart_tpu.cli.fleet_cmds",
    "lint": "kart_tpu.cli.lint_cmds",
    "export": "kart_tpu.cli.tile_cmds",
}


class CliError(click.ClickException):
    exit_code = 2


class Context:
    """Lazily opens the repo for commands that need one
    (reference: kart/context.py)."""

    def __init__(self):
        self.repo_path = os.environ.get("KART_REPO", ".")
        self.user_agent = f"kart_tpu/{kart_tpu.__version__}"

    @property
    def repo(self):
        from kart_tpu.core.repo import KartRepo, NotFound

        try:
            return KartRepo(self.repo_path)
        except NotFound as e:
            raise click.UsageError(str(e))

    def require_state(self, *allowed):
        repo = self.repo
        if repo.state not in allowed:
            from kart_tpu.core.repo import KartRepoState

            raise CliError(KartRepoState.bad_state_message(repo.state, allowed))
        return repo


class KartGroup(click.Group):
    def list_commands(self, ctx):
        return sorted(set(super().list_commands(ctx)) | set(_COMMANDS))

    def get_command(self, ctx, name):
        cmd = super().get_command(ctx, name)
        if cmd is not None:
            return cmd
        module_name = _COMMANDS.get(name)
        if module_name is None:
            return None
        try:
            importlib.import_module(module_name)
        except ImportError as e:
            raise CliError(f"Command {name!r} is unavailable: {e}")
        return super().get_command(ctx, name)


@click.group(cls=KartGroup)
@click.option(
    "-C",
    "repo_dir",
    metavar="PATH",
    default=None,
    help="Run as if started in PATH instead of the current directory",
)
@click.version_option(version=kart_tpu.__version__, prog_name="kart (kart_tpu)")
@click.option("-v", "--verbose", count=True, help="Increase verbosity (-v, -vv)")
@click.option(
    "--trace",
    "trace_flag",
    is_flag=True,
    help="Record a Chrome trace of this command (written on exit; "
    "KART_TRACE=<path> picks the file)",
)
@click.option(
    "--reprobe",
    "reprobe_flag",
    is_flag=True,
    help="Drop the persisted accelerator-probe verdict and probe afresh "
    "(equivalent to KART_JAX_REPROBE=1; see docs/DEVICE.md)",
)
@click.pass_context
def cli(ctx, repo_dir, verbose, trace_flag, reprobe_flag):
    """kart_tpu — TPU-native distributed version control for geospatial data."""
    from kart_tpu import telemetry

    ctx.obj = Context()
    if repo_dir:
        ctx.obj.repo_path = repo_dir
    if reprobe_flag:
        from kart_tpu import runtime

        removed = runtime.invalidate_probe_cache()
        # also re-key every probe this process makes, so the fresh verdict
        # is a real probe even if some library path already consulted it
        os.environ["KART_JAX_REPROBE"] = "1"
        if removed:
            click.echo(f"Dropped cached backend probe verdict ({removed})", err=True)
    # always configured (not only on -v): one kart_tpu logger, one format,
    # KART_LOG honoured for level — servers and library re-entry included
    telemetry.configure_logging(verbose)
    telemetry.enable_from_env()
    if trace_flag and not telemetry.tracing_enabled():
        telemetry.enable(trace=True, trace_path=telemetry.default_trace_path())
    if verbose:
        telemetry.enable(spans=True)  # feeds the end-of-command summary
    # one command = one trace: every transport verb this command issues
    # inherits this root context's trace id, and the wire carries it to
    # the servers (docs/OBSERVABILITY.md §8)
    telemetry.set_root_request(verb=ctx.invoked_subcommand)
    # the command's root span: every main-thread span descends from it, so
    # what no span covers is its self time (docs/OBSERVABILITY.md §2)
    root_span = telemetry.span("cli.command", cmd=ctx.invoked_subcommand)
    root_span.__enter__()

    @ctx.call_on_close
    def _flush_telemetry():
        from kart_tpu.telemetry import sinks

        root_span.__exit__(None, None, None)
        if telemetry.tracing_enabled():
            dropped = telemetry.events_dropped_count()
            path = sinks.write_chrome_trace()
            if path:
                note = (
                    f" ({dropped} span events dropped at the buffer cap)"
                    if dropped
                    else ""
                )
                click.echo(f"Trace written to {path}{note}", err=True)
        if verbose:
            summary = sinks.phase_summary_text()
            if summary:
                click.echo(summary, err=True)


def add_command(name, fn):
    cli.add_command(fn, name=name)


def entrypoint():
    """Translate internal exceptions into clean one-line errors with stable
    exit codes (reference: kart/cli.py entrypoint + kart/exceptions.py)."""
    import sys

    from kart_tpu import exceptions
    from kart_tpu.core.repo import InvalidOperation, NotFound, RepoError
    from kart_tpu.importer import ImportSourceError

    try:
        cli(standalone_mode=True)
    except NotFound as e:
        click.echo(f"Error: {e}", err=True)
        sys.exit(getattr(e, "exit_code", exceptions.NOT_FOUND))
    except ImportSourceError as e:
        click.echo(f"Error: {e}", err=True)
        sys.exit(exceptions.NO_IMPORT_SOURCE)
    except (InvalidOperation, RepoError) as e:
        click.echo(f"Error: {e}", err=True)
        sys.exit(getattr(e, "exit_code", exceptions.INVALID_OPERATION))


if __name__ == "__main__":
    entrypoint()
