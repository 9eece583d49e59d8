"""The benchmark's own arithmetic: the yardstick later PRs may not edit."""
