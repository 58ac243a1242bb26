"""Device time per step, in ms, of the operations whose `op_name` path
says they belong to a part of the program: their self time in the
reduced trace over the whole steps of the traced window, per chip.

An operation's path is the `tf_op` the profiler kept for it
(xplane_scopes.op_scopes; a fusion carries its root's), e.g.
`jit(step_fn)/transpose(jvp(HybridModel))/layer_2/attn/wq/dot_general`:
the transforms, Flax modules and `jax.named_scope`s it was traced under,
by `/`. An element names `x` bare or wrapped: `x`, `jvp(x)`,
`transpose(jvp(x))`, `jit(x)`. An operation counts if

  * an element names one of params['any'] (absent: every operation),
  * no element names one of params['none'], and
  * its pass is params['pass'] (absent: any): `backward` where an
    element opens `transpose(`, else `forward` where one opens `jvp(`,
    else `neither` (the optimizer, copies, what kept no name).

An operation the profiler kept no path for has no elements: it counts
only where `any` is absent. So `forward`, `backward`, the scope
`optimizer`, and `neither` without `optimizer` share out the device's
busy time. A trace without paths, or a program in which nothing
matches, gives None."""
import re

import xplane
import xplane_scopes

_WRAPPED = re.compile(r'^\w+\((.*)\)$')


def names(element: str) -> str:
    """`transpose(jvp(loss))` -> `loss`: what an element names."""
    while True:
        inner = _WRAPPED.match(element)
        if not inner:
            return element
        element = inner.group(1)


def which_pass(elements: list) -> str:
    if any(e.startswith('transpose(') for e in elements):
        return 'backward'
    if any(e.startswith('jvp(') for e in elements):
        return 'forward'
    return 'neither'


def counts(path: str, params: dict) -> bool:
    elements = [e for e in path.rstrip(':').split('/') if e]
    named = {names(e) for e in elements}
    if 'any' in params and not named & set(params['any']):
        return False
    if named & set(params.get('none', ())):
        return False
    return params.get('pass') in (None, which_pass(elements))


def read(obs, params):
    tr = obs.get('trace')
    if not tr or not tr.get('steps') or not obs.get('profile_dir'):
        return None
    if '_op_scopes' not in obs:
        path = xplane.find_trace(obs['profile_dir'])
        obs['_op_scopes'] = xplane_scopes.op_scopes(path) if path else {}
    scopes = obs['_op_scopes']
    if not scopes:
        return None
    hit = sum(s for name, s in tr['ops_s']
              if counts(scopes.get(name, ''), params))
    return 1e3 * hit / (tr['steps'] * tr['chips']) if hit else None
