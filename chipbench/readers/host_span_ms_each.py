"""Host time a turn, in ms, of a span that a thread beside the step loop
writes: the median time of the spans called params['span'] inside the
stretch of the whole `train.step` spans, on whichever line
(xplane_host.each). The prefetcher's producer builds and places one
batch for every step the loop takes, so its turn is a step's share; a
turn that nears the step's time is the input wait to come. The spans
are host_span_ms_per_step's, read once a run. A program that writes no
such span gives None."""
import common
import xplane_host
from readers import host_span_ms_per_step


def read(obs, params):
    if not obs.get('profile_dir'):
        return None
    turns = xplane_host.each(host_span_ms_per_step.spans(obs),
                             params['span'])
    return common.median(turns) if turns else None
