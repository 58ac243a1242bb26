"""skyt — the CLI.

Reference: sky/cli.py (click group :914-934; launch :1038, exec :1167,
status :1513, queue :1902, logs :1964, cancel :2058, stop :2134, autostop
:2212, start :2338, down :2535, check :2901, show_gpus :2954, storage
:3362, jobs :3450, serve :3449). Same verb surface, TPU-first flags.
"""
import os
import sys
from typing import Any, Dict, List, Optional

import click

from skypilot_tpu import exceptions
from skypilot_tpu.utils import log_utils

logger = log_utils.init_logger(__name__)


def _fmt_table(rows: List[List[str]], headers: List[str]) -> str:
    widths = [len(h) for h in headers]
    for row in rows:
        for i, cell in enumerate(row):
            widths[i] = max(widths[i], len(str(cell)))
    fmt = '  '.join(f'{{:<{w}}}' for w in widths)
    lines = [fmt.format(*headers)]
    lines += [fmt.format(*[str(c) for c in row]) for row in rows]
    return '\n'.join(lines)


def _load_task(entrypoint: str, *, name: Optional[str] = None,
               workdir: Optional[str] = None,
               cloud: Optional[str] = None,
               accelerators: Optional[str] = None,
               num_nodes: Optional[int] = None,
               use_spot: Optional[bool] = None,
               envs: Optional[List[str]] = None):
    """YAML path or inline command → Task, with CLI overrides (reference:
    _make_task_or_dag_from_entrypoint_with_overrides, sky/cli.py:696)."""
    from skypilot_tpu import dag as dag_lib
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu import task as task_lib
    if entrypoint.endswith(('.yaml', '.yml')) and os.path.exists(
            entrypoint):
        if dag_lib.yaml_is_pipeline(entrypoint):
            raise click.UsageError(
                f'{entrypoint} is a multi-document pipeline YAML; '
                f'pipelines run as managed jobs: '
                f'`skyt jobs launch {entrypoint}`.')
        task = task_lib.Task.from_yaml(entrypoint)
    else:
        task = task_lib.Task(run=entrypoint)
    if name:
        task.name = name
    if workdir:
        task.workdir = workdir
    if num_nodes:
        task._user_num_nodes = num_nodes  # pylint: disable=protected-access
    override: Dict[str, Any] = {}
    if cloud:
        override['cloud'] = cloud
    if accelerators:
        override['accelerators'] = accelerators
    if use_spot is not None:
        override['use_spot'] = use_spot
    if override:
        base = list(task.resources) or [resources_lib.Resources()]
        task.set_resources({r.copy(**override) for r in base})
    if envs:
        task.update_envs(_parse_envs(envs))
    return task


def _parse_envs(envs: 'List[str]') -> 'Dict[str, str]':
    """--env KEY=VAL pairs -> dict, with a usable error on bad shapes."""
    out: Dict[str, str] = {}
    for e in envs:
        if '=' not in e:
            raise click.UsageError(
                f'--env expects KEY=VAL, got {e!r}')
        k, v = e.split('=', 1)
        out[k] = v
    return out


@click.group()
@click.version_option(message='%(version)s',
                      package_name='skypilot_tpu',
                      version=__import__('skypilot_tpu').__version__)
def cli():
    """skyt: TPU-native cluster launcher and job orchestrator."""


# ------------------------------------------------------------------ launch
@cli.command()
@click.argument('entrypoint', required=True)
@click.option('--cluster', '-c', default=None, help='Cluster name.')
@click.option('--name', '-n', default=None, help='Task name.')
@click.option('--workdir', default=None, type=click.Path(exists=True))
@click.option('--cloud', default=None)
@click.option('--gpus', '--tpus', 'accelerators', default=None,
              help='Accelerator spec, e.g. tpu-v5e-16.')
@click.option('--num-nodes', default=None, type=int)
@click.option('--use-spot/--no-use-spot', default=None)
@click.option('--env', 'envs', multiple=True, help='KEY=VAL.')
@click.option('--detach-run', '-d', is_flag=True, default=False)
@click.option('--dryrun', is_flag=True, default=False)
@click.option('--down', is_flag=True, default=False,
              help='Tear down after the job finishes.')
@click.option('--retry-until-up', '-r', is_flag=True, default=False)
@click.option('--idle-minutes-to-autostop', '-i', default=None, type=int)
@click.option('--yes', '-y', is_flag=True, default=False)
def launch(entrypoint, cluster, name, workdir, cloud, accelerators,
           num_nodes, use_spot, envs, detach_run, dryrun, down,
           retry_until_up, idle_minutes_to_autostop, yes):
    """Launch a task (provision + setup + run). Reference: sky launch."""
    from skypilot_tpu import execution
    task = _load_task(entrypoint, name=name, workdir=workdir, cloud=cloud,
                      accelerators=accelerators, num_nodes=num_nodes,
                      use_spot=use_spot, envs=list(envs))
    if not yes and not dryrun:
        click.confirm(f'Launching task on cluster '
                      f'{cluster or task.name or "skyt-cluster"!r}. '
                      f'Proceed?', default=True, abort=True)
    job_id = execution.launch(
        task, cluster_name=cluster, dryrun=dryrun, down=down,
        detach_run=detach_run, retry_until_up=retry_until_up,
        idle_minutes_to_autostop=idle_minutes_to_autostop)
    if job_id is not None and detach_run:
        click.echo(f'Job submitted, ID: {job_id}')


@cli.command(name='exec')
@click.argument('cluster', required=True)
@click.argument('entrypoint', required=True)
@click.option('--name', '-n', default=None)
@click.option('--workdir', default=None, type=click.Path(exists=True))
@click.option('--env', 'envs', multiple=True)
@click.option('--detach-run', '-d', is_flag=True, default=False)
def exec_cmd(cluster, entrypoint, name, workdir, envs, detach_run):
    """Run a task on an existing cluster (skips provision/setup)."""
    from skypilot_tpu import execution
    task = _load_task(entrypoint, name=name, workdir=workdir,
                      envs=list(envs))
    job_id = execution.exec(task, cluster, detach_run=detach_run)
    if job_id is not None and detach_run:
        click.echo(f'Job submitted, ID: {job_id}')


# ------------------------------------------------------------------ status
@cli.command()
@click.argument('clusters', nargs=-1)
@click.option('--refresh', '-r', is_flag=True, default=False)
def status(clusters, refresh):
    """Show clusters. Reference: sky status."""
    from skypilot_tpu import core
    records = core.status(list(clusters) or None, refresh=refresh)
    if not records:
        click.echo('No existing clusters.')
        return
    rows = []
    for r in records:
        handle = r['handle']
        res = handle.launched_resources
        autostop = (f'{r["autostop"]}m' +
                    ('(down)' if r['to_down'] else '')
                    if r['autostop'] >= 0 else '-')
        rows.append([r['name'], str(res), handle.num_hosts,
                     r['status'].value, autostop])
    click.echo(_fmt_table(rows, ['NAME', 'RESOURCES', 'HOSTS', 'STATUS',
                                 'AUTOSTOP']))


@cli.command()
@click.argument('cluster', required=True)
@click.option('--skip-finished', '-s', is_flag=True, default=False)
def queue(cluster, skip_finished):
    """Show a cluster's job queue. Reference: sky queue."""
    from skypilot_tpu import core
    jobs = core.queue(cluster, skip_finished=skip_finished)
    rows = [[j['job_id'], j.get('name') or '-', j['status'],
             j.get('submitted_at') or '-'] for j in jobs]
    click.echo(_fmt_table(rows, ['ID', 'NAME', 'STATUS', 'SUBMITTED']))


@cli.command()
@click.argument('cluster', required=True)
@click.argument('job_id', required=False, type=int)
@click.option('--no-follow', is_flag=True, default=False)
@click.option('--sync-down', is_flag=True, default=False,
              help='Download logs instead of streaming.')
@click.option('--profile', is_flag=True, default=False,
              help='Download the job\'s jax.profiler trace (the job must '
                   'have run with SKYT_PROFILE=1 in its envs).')
def logs(cluster, job_id, no_follow, sync_down, profile):
    """Tail job logs. Reference: sky logs; --profile is the SURVEY §5
    jax.profiler collection the reference lacks."""
    import os

    from skypilot_tpu import core
    if profile:
        import glob as glob_mod
        if job_id is None:
            raise click.UsageError('--profile needs a JOB_ID')
        path = core.download_logs(cluster, job_id)
        # Logs land per host (host-<rank>/...); traces live inside them.
        prof_dirs = sorted(
            glob_mod.glob(os.path.join(path, '*', 'profile')) +
            glob_mod.glob(os.path.join(path, 'profile')))
        if not prof_dirs:
            raise click.ClickException(
                f'no profile trace in job {job_id} logs — launch with '
                'env SKYT_PROFILE=1 (envs: {SKYT_PROFILE: 1} in the task '
                'YAML) to collect one')
        for d in prof_dirs:
            click.echo(f'Profile trace synced to {d}')
        click.echo(f'View: tensorboard --logdir {prof_dirs[0]}')
        return
    if sync_down:
        if job_id is None:
            raise click.UsageError('--sync-down needs a JOB_ID')
        path = core.download_logs(cluster, job_id)
        click.echo(f'Logs synced to {path}')
        return
    sys.exit(core.tail_logs(cluster, job_id, follow=not no_follow))


@cli.command()
@click.argument('cluster', required=True)
@click.argument('job_ids', nargs=-1, type=int)
@click.option('--all', '-a', 'all_jobs', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def cancel(cluster, job_ids, all_jobs, yes):
    """Cancel jobs. Reference: sky cancel."""
    from skypilot_tpu import core
    if not job_ids and not all_jobs:
        raise click.UsageError('Provide JOB_IDS or --all.')
    if not yes:
        what = 'ALL jobs' if all_jobs else f'jobs {list(job_ids)}'
        click.confirm(f'Cancel {what} on {cluster!r}?', default=True,
                      abort=True)
    cancelled = core.cancel(cluster, list(job_ids) or None,
                            all_jobs=all_jobs)
    click.echo(f'Cancelled: {cancelled or "none"}')


# --------------------------------------------------------------- lifecycle
@cli.command()
@click.argument('clusters', nargs=-1, required=True)
@click.option('--yes', '-y', is_flag=True, default=False)
def stop(clusters, yes):
    """Stop clusters (restartable). Reference: sky stop."""
    from skypilot_tpu import core
    for name in clusters:
        if not yes:
            click.confirm(f'Stop {name!r}?', default=True, abort=True)
        core.stop(name)
        click.echo(f'Cluster {name} stopped.')


@cli.command()
@click.argument('clusters', nargs=-1, required=True)
@click.option('--retry-until-up', '-r', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def start(clusters, retry_until_up, yes):
    """Restart stopped clusters. Reference: sky start."""
    from skypilot_tpu import core
    for name in clusters:
        if not yes:
            click.confirm(f'Start {name!r}?', default=True, abort=True)
        core.start(name, retry_until_up=retry_until_up)
        click.echo(f'Cluster {name} started.')


@cli.command()
@click.argument('clusters', nargs=-1, required=True)
@click.option('--purge', is_flag=True, default=False,
              help='Remove state even if cloud teardown fails.')
@click.option('--yes', '-y', is_flag=True, default=False)
def down(clusters, purge, yes):
    """Terminate clusters. Reference: sky down."""
    from skypilot_tpu import core
    for name in clusters:
        if not yes:
            click.confirm(f'Terminate {name!r}?', default=True,
                          abort=True)
        core.down(name, purge=purge)
        click.echo(f'Cluster {name} terminated.')


@cli.command()
@click.argument('cluster', required=True)
@click.option('--idle-minutes', '-i', default=None, type=int)
@click.option('--down', is_flag=True, default=False,
              help='Terminate instead of stop when idle.')
@click.option('--cancel', 'cancel_autostop', is_flag=True, default=False)
def autostop(cluster, idle_minutes, down, cancel_autostop):
    """Schedule autostop. Reference: sky autostop."""
    from skypilot_tpu import core
    if cancel_autostop:
        idle_minutes = -1
    elif idle_minutes is None:
        raise click.UsageError('Pass --idle-minutes N or --cancel.')
    core.autostop(cluster, idle_minutes, down=down)
    if idle_minutes < 0:
        click.echo(f'Autostop cancelled on {cluster}.')
    else:
        click.echo(f'{cluster} will {"terminate" if down else "stop"} '
                   f'after {idle_minutes} idle minutes.')


# ------------------------------------------------------------------- info
@cli.command()
def check():
    """Probe cloud credentials. Reference: sky check."""
    from skypilot_tpu import check as check_lib
    enabled = check_lib.check()
    click.echo(f'Enabled clouds: {", ".join(enabled) or "none"}')


@cli.command(name='show-tpus')
@click.option('--cloud', default='gcp')
@click.option('--all', '-a', 'show_all', is_flag=True, default=False,
              help='Include GPU/CPU offerings.')
def show_tpus(cloud, show_all):
    """List TPU (and optionally GPU) offerings with prices.

    Reference: sky show-gpus."""
    from skypilot_tpu import catalog
    by_acc = catalog.list_accelerators(cloud)
    rows = []
    for acc_name, offs in sorted(by_acc.items()):
        if not show_all and not acc_name.startswith('tpu'):
            continue
        for off in offs:
            rows.append([acc_name, off.region, off.zone or '-',
                         f'${off.price:.2f}'
                         if off.price is not None else '-',
                         f'${off.spot_price:.2f}'
                         if off.spot_price is not None else '-'])
    click.echo(_fmt_table(rows, ['ACCELERATOR', 'REGION', 'ZONE', '$/H',
                                 'SPOT $/H']))
    _warn_stale_catalog(cloud)


def _warn_stale_catalog(cloud: str = 'gcp') -> None:
    """Price-bearing outputs carry a staleness note: the static catalog
    silently ages."""
    if cloud != 'gcp':
        return
    from skypilot_tpu.catalog import common as catalog_common
    msg = catalog_common.staleness_warning('gcp')
    if msg:
        click.secho(f'Note: {msg}', fg='yellow', err=True)


@cli.command(name='cost-report')
def cost_report():
    """Accumulated cluster costs. Reference: sky cost-report."""
    from skypilot_tpu import core
    rows = []
    for r in core.cost_report():
        hours = r['duration_s'] / 3600.0
        rows.append([r['name'], r['num_nodes'], f'{hours:.1f}h',
                     f'${r["cost"]:.2f}'])
    click.echo(_fmt_table(rows, ['NAME', 'HOSTS', 'UPTIME', 'COST']))
    _warn_stale_catalog()


# ---------------------------------------------------------------- storage
@cli.group()
def storage():
    """Storage management. Reference: sky storage."""


@storage.command(name='ls')
def storage_ls():
    from skypilot_tpu import core
    rows = [[s['name'], s['status'].value] for s in core.storage_ls()]
    click.echo(_fmt_table(rows, ['NAME', 'STATUS']))


@storage.command(name='delete')
@click.argument('names', nargs=-1, required=True)
@click.option('--yes', '-y', is_flag=True, default=False)
def storage_delete(names, yes):
    from skypilot_tpu import core
    for name in names:
        if not yes:
            click.confirm(f'Delete storage {name!r}?', default=True,
                          abort=True)
        core.storage_delete(name)
        click.echo(f'Storage {name} deleted.')


# ------------------------------------------------------------------- jobs
@cli.group()
def jobs():
    """Managed jobs with preemption recovery. Reference: sky jobs."""


@jobs.command(name='launch')
@click.argument('entrypoint', required=True)
@click.option('--name', '-n', default=None)
@click.option('--workdir', default=None, type=click.Path(exists=True))
@click.option('--cloud', default=None)
@click.option('--gpus', '--tpus', 'accelerators', default=None)
@click.option('--num-nodes', default=None, type=int)
@click.option('--use-spot/--no-use-spot', default=None)
@click.option('--env', 'envs', multiple=True, help='KEY=VAL.')
@click.option('--retry-until-up/--no-retry-until-up', default=True)
@click.option('--detach-run', '-d', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_launch(entrypoint, name, workdir, cloud, accelerators, num_nodes,
                use_spot, envs, retry_until_up, detach_run, yes):
    """Launch a managed job (single task, or a multi-document pipeline
    YAML run as a chain DAG). Reference: sky jobs launch (cli.py:3500)."""
    from skypilot_tpu import dag as dag_lib
    from skypilot_tpu.jobs import core as jobs_core
    task = None
    if entrypoint.endswith(('.yaml', '.yml')) and os.path.exists(
            entrypoint):
        env_overrides = _parse_envs(envs) if envs else None
        task = dag_lib.maybe_load_pipeline(entrypoint, env_overrides)
    if task is not None:
        # Per-task resource overrides are ambiguous across a pipeline's
        # stages — fail loud instead of silently dropping them.
        dropped = [f for f, v in [('--workdir', workdir),
                                  ('--cloud', cloud),
                                  ('--accelerators', accelerators),
                                  ('--num-nodes', num_nodes),
                                  ('--use-spot', use_spot)]
                   if v is not None]
        if dropped:
            raise click.UsageError(
                f'{", ".join(dropped)} cannot override a multi-stage '
                f'pipeline YAML; set per-stage values in the YAML.')
    else:
        task = _load_task(entrypoint, name=name, workdir=workdir,
                          cloud=cloud, accelerators=accelerators,
                          num_nodes=num_nodes, use_spot=use_spot,
                          envs=envs)
    label = name or task.name or '?'
    if not yes:
        click.confirm(f'Launch managed job {label!r}?',
                      default=True, abort=True)
    job_id = jobs_core.launch(task, name or task.name,
                              retry_until_up=retry_until_up,
                              detach=detach_run)
    click.echo(f'Managed job {job_id} submitted.')


@jobs.command(name='queue')
@click.option('--skip-finished', '-s', is_flag=True, default=False)
def jobs_queue(skip_finished):
    """Reference: sky jobs queue."""
    from skypilot_tpu.jobs import core as jobs_core
    rows = []
    for j in jobs_core.queue(skip_finished=skip_finished):
        rows.append([j['job_id'], j['name'] or '-', j['status'].value,
                     j['recovery_count'],
                     j.get('failure_reason') or '-'])
    click.echo(_fmt_table(rows, ['ID', 'NAME', 'STATUS', 'RECOVERIES',
                                 'REASON']))


@jobs.command(name='cancel')
@click.argument('job_ids', nargs=-1, type=int)
@click.option('--all', '-a', 'all_jobs', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def jobs_cancel(job_ids, all_jobs, yes):
    """Reference: sky jobs cancel."""
    from skypilot_tpu.jobs import core as jobs_core
    if not job_ids and not all_jobs:
        raise click.UsageError('Provide JOB_IDS or --all.')
    if not yes:
        what = 'ALL managed jobs' if all_jobs else f'jobs {list(job_ids)}'
        click.confirm(f'Cancel {what}?', default=True, abort=True)
    cancelled = jobs_core.cancel(list(job_ids) or None, all_jobs=all_jobs)
    click.echo(f'Cancelled: {cancelled or "none"}')


@jobs.command(name='logs')
@click.argument('job_id', required=False, type=int)
@click.option('--controller', is_flag=True, default=False,
              help='Tail the controller process log instead.')
@click.option('--no-follow', is_flag=True, default=False)
def jobs_logs(job_id, controller, no_follow):
    """Reference: sky jobs logs."""
    from skypilot_tpu.jobs import core as jobs_core
    sys.exit(jobs_core.tail_logs(job_id, follow=not no_follow,
                                 controller=controller))


@cli.command()
@click.option('--port', default=None, type=int)
def dashboard(port):
    """Web dashboard of clusters/jobs/services. Reference: sky jobs
    dashboard."""
    from skypilot_tpu import dashboard as dashboard_lib
    dashboard_lib.run(port if port is not None
                      else dashboard_lib.DEFAULT_PORT)


# ------------------------------------------------------------------ bench
@cli.group()
def bench():
    """Benchmark a task across candidate resources. Reference: sky
    bench."""


@bench.command(name='launch')
@click.argument('entrypoint', required=True)
@click.option('--benchmark', '-b', 'benchmark_name', required=True)
@click.option('--yes', '-y', is_flag=True, default=False)
def bench_launch(entrypoint, benchmark_name, yes):
    """Launch one cluster per candidate resource (task `any_of`)."""
    from skypilot_tpu.benchmark import benchmark_state
    from skypilot_tpu.benchmark import benchmark_utils
    task = _load_task(entrypoint)
    candidates = benchmark_utils.generate_benchmark_candidates(task)
    if not candidates:
        raise click.UsageError(
            'The task has no resources to benchmark — use a YAML with a '
            '`resources:` section (`any_of:` fans out candidates).')
    if benchmark_state.get_benchmark(benchmark_name) is not None:
        raise click.UsageError(
            f'Benchmark {benchmark_name!r} already exists. '
            f'`skyt bench down {benchmark_name}` and '
            f'`skyt bench delete {benchmark_name}` first.')
    if not yes:
        click.confirm(
            f'Launch {len(candidates)} benchmark clusters?', default=True,
            abort=True)
    benchmark_state.add_benchmark(benchmark_name, entrypoint)
    clusters = benchmark_utils.launch_benchmark_clusters(
        benchmark_name, task, candidates)
    click.echo(f'Benchmark {benchmark_name}: launched {clusters}')


@bench.command(name='show')
@click.argument('benchmark_name', required=True)
def bench_show(benchmark_name):
    """Show interpolated $/step and ETA per candidate."""
    from skypilot_tpu.benchmark import benchmark_utils
    benchmark_utils.update_benchmark_results(benchmark_name)
    rows = []
    for r in benchmark_utils.report(benchmark_name):
        def _fmt(val, spec):
            return format(val, spec) if val is not None else '-'
        rows.append([
            r['cluster'], str(r['resources']), r['status'],
            f"${r['hourly_cost']:.2f}",
            _fmt(r['num_steps'], 'd'),
            _fmt(r['seconds_per_step'], '.3f'),
            ('$' + _fmt(r['cost_per_step'], '.6f'))
            if r['cost_per_step'] is not None else '-',
            (_fmt(r['eta_s'], '.0f') + 's')
            if r['eta_s'] is not None else '-',
        ])
    click.echo(_fmt_table(rows, ['CLUSTER', 'RESOURCES', 'STATUS', '$/HR',
                                 'STEPS', 'S/STEP', '$/STEP', 'ETA']))


@bench.command(name='ls')
def bench_ls():
    from skypilot_tpu.benchmark import benchmark_state
    rows = [[b['name'], b['task_yaml']]
            for b in benchmark_state.get_benchmarks()]
    click.echo(_fmt_table(rows, ['BENCHMARK', 'TASK']))


@bench.command(name='down')
@click.argument('benchmark_name', required=True)
@click.option('--yes', '-y', is_flag=True, default=False)
def bench_down(benchmark_name, yes):
    """Terminate all clusters of a benchmark."""
    from skypilot_tpu.benchmark import benchmark_utils
    if not yes:
        click.confirm(f'Terminate benchmark {benchmark_name!r} clusters?',
                      default=True, abort=True)
    benchmark_utils.terminate_benchmark_clusters(benchmark_name)
    click.echo('Done.')


@bench.command(name='delete')
@click.argument('benchmark_name', required=True)
@click.option('--yes', '-y', is_flag=True, default=False)
def bench_delete(benchmark_name, yes):
    from skypilot_tpu.benchmark import benchmark_state
    live = [r['cluster'] for r in
            benchmark_state.get_results(benchmark_name)
            if r['status'] is not
            benchmark_state.BenchmarkStatus.TERMINATED]
    if live:
        raise click.UsageError(
            f'Benchmark {benchmark_name!r} still has clusters {live}; '
            f'run `skyt bench down {benchmark_name}` first.')
    if not yes:
        click.confirm(f'Delete benchmark {benchmark_name!r} records?',
                      default=True, abort=True)
    benchmark_state.remove_benchmark(benchmark_name)
    click.echo(f'Benchmark {benchmark_name} deleted.')


# ------------------------------------------------------------------ serve
@cli.group()
def serve():
    """Autoscaled model serving. Reference: sky serve."""


@serve.command(name='up')
@click.argument('entrypoint', required=True)
@click.option('--service-name', '-n', default=None)
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_up(entrypoint, service_name, yes):
    """Start a service. Reference: sky serve up."""
    from skypilot_tpu.serve import core as serve_core
    task = _load_task(entrypoint)
    if not yes:
        click.confirm(
            f'Start service {service_name or task.name or "?"!r}?',
            default=True, abort=True)
    name, endpoint = serve_core.up(task, service_name)
    click.echo(f'Service {name} starting. Endpoint: {endpoint}')


@serve.command(name='update')
@click.argument('service_name', required=True)
@click.argument('entrypoint', required=True)
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_update(service_name, entrypoint, yes):
    """Rolling-update a service. Reference: sky serve update."""
    from skypilot_tpu.serve import core as serve_core
    task = _load_task(entrypoint)
    if not yes:
        click.confirm(f'Update service {service_name!r}?', default=True,
                      abort=True)
    version = serve_core.update(task, service_name)
    click.echo(f'Service {service_name} rolling to version {version}.')


@serve.command(name='down')
@click.argument('service_names', nargs=-1, required=True)
@click.option('--purge', '-p', is_flag=True, default=False)
@click.option('--yes', '-y', is_flag=True, default=False)
def serve_down(service_names, purge, yes):
    """Tear down service(s). Reference: sky serve down."""
    from skypilot_tpu.serve import core as serve_core
    for name in service_names:
        if not yes:
            click.confirm(f'Tear down service {name!r}?', default=True,
                          abort=True)
        serve_core.down(name, purge=purge)
        click.echo(f'Service {name} terminated.')


def _replica_perf(r) -> str:
    """PERF cell for `serve status` from a replica's /stats snapshot.
    The snapshot comes from an arbitrary replica's HTTP response —
    every field is untrusted, so a mis-shaped payload renders '-' for
    that replica instead of crashing the whole command."""
    s = r.get('stats')
    if not isinstance(s, dict):
        return '-'
    parts = []
    ttft = s.get('ttft_ms')
    if isinstance(ttft, dict) and isinstance(ttft.get('p50'),
                                             (int, float)):
        parts.append(f"p50 {ttft['p50']}ms")
    rate = s.get('steady_decode_tok_per_sec')
    if isinstance(rate, (int, float)) and rate:
        parts.append(f'{rate:.0f} tok/s')
    if isinstance(s.get('active_slots'), int) and \
            isinstance(s.get('num_slots'), int):
        parts.append(f"slots {s['active_slots']}/{s['num_slots']}")
    return ' '.join(parts) or '-'


@serve.command(name='status')
@click.argument('service_names', nargs=-1)
def serve_status(service_names):
    """Reference: sky serve status."""
    from skypilot_tpu.serve import core as serve_core
    for svc in serve_core.status(list(service_names) or None):
        click.echo(f'{svc["name"]}: {svc["status"].value} '
                   f'(v{svc["version"]}) endpoint={svc["endpoint"]}')
        ro = svc.get('rollout')
        if ro:
            detail = f' ({ro["error"]})' if ro.get('error') else ''
            click.echo(f'  rollout: v{ro.get("baseline_version")}'
                       f'->v{ro.get("target_version")} '
                       f'phase={ro.get("phase")} '
                       f'updated={len(ro.get("updated") or [])}'
                       f'{detail}')
        asc = svc.get('autoscaler')
        if isinstance(asc, dict):
            line = (f'  autoscaler: mode={asc.get("mode")} '
                    f'target={asc.get("target_num_replicas")}')
            fc = asc.get('forecast')
            if isinstance(fc, dict) and \
                    fc.get('qps_at_lead') is not None:
                line += (f' forecast={fc["qps_at_lead"]}qps'
                         f'@+{fc.get("lead_s")}s')
            last = asc.get('last_decision')
            if isinstance(last, dict):
                line += f' last={last.get("reason")}'
            click.echo(line)
        rs = svc.get('reshard')
        if isinstance(rs, dict):
            detail = f' ({rs["error"]})' if rs.get('error') else ''
            click.echo(f'  reshard: ->{rs.get("target_nodes")} '
                       f'virtual nodes phase={rs.get("phase")} '
                       f'updated={len(rs.get("updated") or [])}'
                       f'{detail}')
        rows = [[r['replica_id'], r['cluster_name'],
                 r['status'].value, r['endpoint'] or '-',
                 f'{r["version"]}/w{r.get("weight_version", 1)}',
                 _replica_perf(r)] for r in svc['replicas']]
        click.echo(_fmt_table(rows, ['ID', 'CLUSTER', 'STATUS',
                                     'ENDPOINT', 'VERSION', 'PERF']))


@serve.command(name='logs')
@click.argument('service_name', required=True)
@click.option('--replica-id', type=int, default=None,
              help='Tail this replica\'s cluster log instead.')
@click.option('--follow/--no-follow', default=False)
def serve_logs(service_name, replica_id, follow):
    """Reference: sky serve logs."""
    from skypilot_tpu.serve import core as serve_core
    target = 'replica' if replica_id is not None else 'controller'
    sys.exit(serve_core.tail_logs(service_name, target=target,
                                  replica_id=replica_id, follow=follow))


def main() -> None:
    try:
        cli()
    except exceptions.SkyTpuError as e:
        click.echo(f'Error: {e}', err=True)
        sys.exit(1)


if __name__ == '__main__':
    main()
