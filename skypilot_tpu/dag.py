"""DAG of tasks with a thread-local `with Dag():` context.

Mirrors the reference's sky/dag.py:7 (networkx DiGraph wrapper + `>>`
chaining) with the same tiny surface: add/remove tasks, chain edges,
is_chain(), tasks property, context manager — plus the multi-document
pipeline-YAML loader (reference: sky/utils/dag_utils.py
load_chain_dag_from_yaml).
"""
import os
import threading
from typing import List, Optional

import yaml


def _nx():
    # Imported where a Dag is built or walked (0.3 s): the agents and
    # controllers import this module and build none.
    import networkx
    return networkx


class Dag:
    def __init__(self, name: Optional[str] = None) -> None:
        self.name = name
        self.graph = _nx().DiGraph()
        self.tasks: List['Task'] = []  # insertion order  # noqa: F821

    def add(self, task) -> None:
        self.graph.add_node(task)
        self.tasks.append(task)

    def remove(self, task) -> None:
        self.graph.remove_node(task)
        self.tasks.remove(task)

    def add_edge(self, op1, op2) -> None:
        assert op1 in self.graph.nodes
        assert op2 in self.graph.nodes
        self.graph.add_edge(op1, op2)

    def __len__(self) -> int:
        return len(self.tasks)

    def __enter__(self) -> 'Dag':
        push_dag(self)
        return self

    def __exit__(self, *args) -> None:
        pop_dag()

    def __repr__(self) -> str:
        pre = f'Dag({self.name})' if self.name else 'Dag'
        return f'{pre}<{len(self.tasks)} task(s)>'

    def is_chain(self) -> bool:
        """True iff the DAG is a linear chain (reference: sky/dag.py:53)."""
        nodes = list(self.graph.nodes)
        out_degrees = [self.graph.out_degree(n) for n in nodes]
        in_degrees = [self.graph.in_degree(n) for n in nodes]
        return (len(nodes) <= 1 or
                (_nx().is_directed_acyclic_graph(self.graph) and
                 all(d <= 1 for d in out_degrees) and
                 all(d <= 1 for d in in_degrees) and
                 sum(out_degrees) == len(nodes) - 1))

    def get_sorted_tasks(self) -> List['Task']:  # noqa: F821
        return list(_nx().topological_sort(self.graph))

    def validate(self) -> None:
        if not _nx().is_directed_acyclic_graph(self.graph):
            raise ValueError('DAG has a cycle.')


class _DagContext(threading.local):
    """Thread-local stack of active Dags (reference: sky/dag.py:71)."""

    def __init__(self):
        super().__init__()
        self._stack: List[Dag] = []

    def push(self, dag: Dag) -> None:
        self._stack.append(dag)

    def pop(self) -> Dag:
        return self._stack.pop()

    def current(self) -> Optional[Dag]:
        return self._stack[-1] if self._stack else None


_dag_context = _DagContext()
push_dag = _dag_context.push
pop_dag = _dag_context.pop
get_current_dag = _dag_context.current


def _read_yaml_docs(path: str) -> List[dict]:
    with open(os.path.expanduser(path), 'r', encoding='utf-8') as f:
        return [c for c in yaml.safe_load_all(f) if c is not None]


def _dag_from_docs(docs: List[dict], path: str,
                   env_overrides: Optional[dict]) -> Dag:
    from skypilot_tpu import exceptions
    from skypilot_tpu import task as task_lib

    for i, d in enumerate(docs):
        if not isinstance(d, dict):
            raise exceptions.InvalidTaskError(
                f'pipeline YAML {path} document {i} must be a mapping, '
                f'got {type(d).__name__}')
    name = None
    if docs and set(docs[0]) == {'name'}:
        name = docs[0]['name']
        docs = docs[1:]
    if not docs:
        raise ValueError(f'pipeline YAML {path} has no task documents')
    with Dag(name) as dag:
        prev = None
        for cfg in docs:
            t = task_lib.Task.from_yaml_config(cfg, env_overrides)
            if prev is not None:
                prev >> t  # pylint: disable=pointless-statement
            prev = t
    return dag


def load_chain_dag_from_yaml(path: str,
                             env_overrides: Optional[dict] = None
                             ) -> Dag:
    """Multi-document pipeline YAML -> chain Dag.

    Document 0 may be a bare ``{name: ...}`` mapping naming the
    pipeline; every other document is a task, chained in file order
    (reference: sky/utils/dag_utils.py load_chain_dag_from_yaml — the
    `sky jobs launch pipeline.yaml` format).
    """
    return _dag_from_docs(_read_yaml_docs(path), path, env_overrides)


def maybe_load_pipeline(path: str,
                        env_overrides: Optional[dict] = None
                        ) -> Optional[Dag]:
    """One parse: a chain Dag when the YAML is multi-document (even a
    named single-stage pipeline), else None (single-doc task files go
    through Task.from_yaml, which handles overrides)."""
    try:
        docs = _read_yaml_docs(path)
    except yaml.YAMLError:
        return None
    if len(docs) <= 1:
        return None
    return _dag_from_docs(docs, path, env_overrides)


def yaml_is_pipeline(path: str) -> bool:
    """True if the YAML file is multi-document (the pipeline format)."""
    try:
        return len(_read_yaml_docs(path)) > 1
    except yaml.YAMLError:
        return False
