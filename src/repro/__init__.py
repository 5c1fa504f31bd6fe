"""repro: hybrid ZO/FO split federated learning reproduction."""
