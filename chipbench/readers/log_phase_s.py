"""Seconds of one phase of set-up (params['phase'], e.g. `runtime`) as
the program measured it: the `<phase>=<s>` of sft's one
`setup phases: imports=... total=...` line in the child's log. A
program that prints no such line gives None."""
import re


def read(obs, params):
    try:
        with open(obs['log'], encoding='utf-8', errors='replace') as f:
            line = re.search(r'setup phases: (.*)', f.read())
    except (OSError, KeyError):
        return None
    part = line and re.search(
        rf'(?<![\w.]){re.escape(params["phase"])}=([0-9.]+)', line.group(1))
    return float(part.group(1)) if part else None
