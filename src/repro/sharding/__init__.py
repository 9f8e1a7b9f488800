from repro.sharding.rules import (  # noqa: F401
    Rules,
    current_rules,
    logical_spec,
    shard,
    use_rules,
)
