"""JSON schemas for the task YAML / config YAML DSL.

Single source of truth for the spec surface, mirroring the reference's
sky/utils/schemas.py (914 LoC). Validated with `jsonschema`.
"""
from typing import Any, Dict

from skypilot_tpu import exceptions

_RESOURCES_SCHEMA = {
    'type': 'object',
    'additionalProperties': False,
    'properties': {
        'cloud': {'type': 'string'},
        'region': {'type': 'string'},
        'zone': {'type': 'string'},
        'instance_type': {'type': 'string'},
        'accelerators': {
            'anyOf': [{'type': 'string'},
                      {'type': 'object',
                       'additionalProperties': {'type': 'integer'}}]
        },
        'cpus': {'anyOf': [{'type': 'integer'}, {'type': 'string'}]},
        'memory': {'anyOf': [{'type': 'integer'}, {'type': 'string'}]},
        'use_spot': {'type': 'boolean'},
        'num_slices': {'type': 'integer', 'minimum': 1},
        'spot_recovery': {'type': 'string'},
        'job_recovery': {'type': 'string'},
        'disk_size': {'type': 'integer'},
        'disk_tier': {'enum': ['low', 'medium', 'high', 'best']},
        'image_id': {'type': 'string'},
        'ports': {
            'anyOf': [
                {'type': 'integer'}, {'type': 'string'},
                {'type': 'array',
                 'items': {'anyOf': [{'type': 'integer'},
                                     {'type': 'string'}]}},
            ]
        },
        'labels': {'type': 'object',
                   'additionalProperties': {'type': 'string'}},
        'runtime_version': {'type': 'string'},
        'reserved': {'type': 'boolean'},
        'autostop': {'anyOf': [{'type': 'integer'}, {'type': 'boolean'}]},
        'any_of': {'type': 'array'},  # candidate resources list
    },
}

_STORAGE_SCHEMA = {
    'type': 'object',
    'additionalProperties': False,
    'properties': {
        'name': {'type': 'string'},
        'source': {
            'anyOf': [{'type': 'string'},
                      {'type': 'array', 'items': {'type': 'string'}}]
        },
        'store': {'enum': ['gcs', 's3']},
        'persistent': {'type': 'boolean'},
        'mode': {'enum': ['MOUNT', 'COPY', 'mount', 'copy']},
    },
}

_SERVICE_SCHEMA = {
    'type': 'object',
    'additionalProperties': False,
    'required': ['readiness_probe'],
    'properties': {
        'readiness_probe': {
            'anyOf': [
                {'type': 'string'},
                {
                    'type': 'object',
                    'additionalProperties': False,
                    'required': ['path'],
                    'properties': {
                        'path': {'type': 'string'},
                        'initial_delay_seconds': {'type': 'number'},
                        'post_data': {
                            'anyOf': [{'type': 'string'}, {'type': 'object'}]
                        },
                        'timeout_seconds': {'type': 'number'},
                    },
                },
            ]
        },
        'replica_policy': {
            'type': 'object',
            'additionalProperties': False,
            'properties': {
                'min_replicas': {'type': 'integer', 'minimum': 0},
                'max_replicas': {'type': 'integer', 'minimum': 0},
                'target_qps_per_replica': {'type': 'number'},
                'upscale_delay_seconds': {'type': 'number'},
                'downscale_delay_seconds': {'type': 'number'},
                'base_ondemand_fallback_replicas': {'type': 'integer'},
            },
        },
        'replicas': {'type': 'integer'},  # shorthand for fixed replica count
        'load_balancing_policy': {
            'enum': ['round_robin', 'least_connections',
                     'prefix_affinity'],
        },
        # Weights checkpoint the service serves (docs/robustness.md
        # "Zero-downtime rollouts"): a spec bump that changes ONLY
        # this field rolls out as an in-place weight hot-swap instead
        # of a drain+relaunch.
        'weights': {'type': 'string'},
    },
}

TASK_SCHEMA = {
    'type': 'object',
    'additionalProperties': False,
    'properties': {
        'name': {'type': 'string'},
        'workdir': {'type': 'string'},
        'setup': {'type': 'string'},
        'run': {'type': 'string'},
        'envs': {'type': 'object',
                 'additionalProperties': {
                     'anyOf': [{'type': 'string'}, {'type': 'number'},
                               {'type': 'null'}]}},
        'num_nodes': {'type': 'integer', 'minimum': 1},
        'resources': _RESOURCES_SCHEMA,
        'file_mounts': {'type': 'object'},
        'storage_mounts': {'type': 'object'},
        'service': _SERVICE_SCHEMA,
    },
}

CONFIG_SCHEMA = {
    'type': 'object',
    'additionalProperties': False,
    'properties': {
        'gcp': {
            'type': 'object',
            'additionalProperties': False,
            'properties': {
                'project_id': {'type': 'string'},
                'vpc_name': {'type': 'string'},
                'service_account': {'type': 'string'},
                'specific_reservations': {'type': 'array'},
            },
        },
        'jobs': {
            'type': 'object',
            'additionalProperties': False,
            'properties': {'controller': {'type': 'object'}},
        },
        'serve': {
            'type': 'object',
            'additionalProperties': False,
            'properties': {'controller': {'type': 'object'}},
        },
        'allowed_clouds': {'type': 'array', 'items': {'type': 'string'}},
    },
}


def _validate(config: Dict[str, Any], schema: Dict[str, Any],
              what: str) -> None:
    # Imported at first use: jsonschema's format checkers cost 1.5 s to
    # import, and every agent, controller and CLI process imports this
    # module through the package root though few of them validate.
    import jsonschema
    try:
        jsonschema.validate(instance=config, schema=schema)
    except jsonschema.ValidationError as e:
        path = '.'.join(str(p) for p in e.absolute_path) or '<root>'
        raise exceptions.InvalidTaskError(
            f'Invalid {what} (at {path}): {e.message}') from None


def validate_task_config(config: Dict[str, Any]) -> None:
    _validate(config, TASK_SCHEMA, 'task YAML')


def validate_resources_config(config: Dict[str, Any]) -> None:
    _validate(config, _RESOURCES_SCHEMA, 'resources')


def validate_service_config(config: Dict[str, Any]) -> None:
    _validate(config, _SERVICE_SCHEMA, 'service spec')


def validate_config_file(config: Dict[str, Any]) -> None:
    _validate(config, CONFIG_SCHEMA, 'config file')
