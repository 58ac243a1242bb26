"""Runs one of the program's entry modules as `python -m <module>` would
and adds what the benchmark needs and the module does not print when it
is stopped by SIGTERM: a line per backend compile as it happens, and
the device's memory and the compile cache's counters at exit.

    python chipbench/children/entry_child.py <module> [the module's flags]

The module runs as __main__ in this process, unchanged; only this
process holds the chip.
"""
import json
import runpy
import sys


def main() -> None:
    module, sys.argv = sys.argv[1], sys.argv[1:]
    import jax

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event == '/jax/core/compile/backend_compile_duration':
            print(f'chipbench-compile: {duration:.3f}', flush=True)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        runpy.run_module(module, run_name='__main__', alter_sys=True)
    finally:
        from skypilot_tpu.ops import dispatch
        from skypilot_tpu.utils import compile_cache
        print('chipbench-exit: ' + json.dumps(
            {'device': dispatch.device_info(),
             'compile_cache': compile_cache.snapshot()}), flush=True)


if __name__ == '__main__':
    main()
