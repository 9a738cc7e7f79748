"""The benchmark's own code: the yardstick later PRs may add to, not edit."""
